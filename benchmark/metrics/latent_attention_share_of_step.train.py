"""Per cent of the train step's device time under `dl4j.latentattention`,
every part, both passes: the projections, the bottleneck's norm, the rotation
where the configuration has one, the flash kernels and the output product of
every latent-attention sub-layer — the new mechanism whole, where
`flash_share_of_step.train` is its kernels alone. Left out where no latent
attention ran under a scope."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, lambda layer, kind, parts: kind == "latentattention")
