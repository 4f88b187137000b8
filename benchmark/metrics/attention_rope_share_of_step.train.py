"""Per cent of the train step's device time under a `GatedAttention`'s `rope`,
both passes: the half-split rotation of q and k — with the split into heads
where it is the kernel pair `dl4j_rope_fwd` / `dl4j_rope_bwd` —, its
recompute and its backward (`rope_share_of_step.train` reads latent
attention's alone). Left out where no `rope` ran under that kind (a layer that
knows no positions, a program without the part)."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(
        run, lambda layer, kind, parts: kind == "gatedattention" and "rope" in parts)
