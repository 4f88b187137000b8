"""Per cent of the train step's device time under `dl4j.gatedattention`,
every part, both passes: the projections, the split into heads and the
rotation, the head gates, the flash kernels — windowed and global — and the
output product of every attention sub-layer, their recompute and their
backward: what the attention stack is of this step, where
`flash_share_of_step.train` is its kernels alone. Left out where no such
layer ran under a scope."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, lambda layer, kind, parts: kind == "gatedattention")
