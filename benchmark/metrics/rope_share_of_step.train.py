"""Per cent of the train step's device time under a latent-attention layer's
`rope`, both passes: the rotation of the queries' rope part a head and of the
one key part all heads share, its recompute and its backward. Left out where
no `rope` ran under a scope (a layer that knows no positions, a program
without the part)."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(
        run, lambda layer, kind, parts: kind == "latentattention" and "rope" in parts)
