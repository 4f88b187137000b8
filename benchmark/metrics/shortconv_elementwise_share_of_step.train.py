"""Per cent of the train step's device time under a short-convolution mixer's
`gates` and `conv`, both passes: B z, the three-tap depthwise convolution and
C c — the bytes-bound passes between the mixer's two products, what a fused
gate-convolution-gate kernel would take (`shortconv_share_of_step.train`
minus this is the two products). Left out where no such part ran under a
scope."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(
        run, lambda layer, kind, parts: kind == "gatedshortconv"
        and parts[:1] and parts[0] in ("gates", "conv"))
