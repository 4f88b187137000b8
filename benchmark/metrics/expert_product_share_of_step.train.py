"""Per cent of the train step's device time under
`dl4j.routedexperts/product`, both passes: the two grouped products
(`ops.linear.grouped_dot`) of every expert layer, the activation between
them and their weight gradients — the experts without their router, sort,
gathers and shared expert (`expert_share_of_step.train` is all of those).
Left out where no routed experts ran under a scope."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(
        run, lambda layer, kind, parts: kind == "routedexperts" and parts[:1] == ("product",))
