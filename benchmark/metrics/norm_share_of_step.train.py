"""Per cent of the train step's device time under a block's `norm` part or a
norm layer of its own (`dl4j.rmsnorm`), both passes: the RMS norms — in a
sandwich-normed looped stack four a layer and pass and the final norm a pass,
each a float32 pass over [t, f] bound by bytes. Left out where no norm ran
under a scope."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(
        run, lambda layer, kind, parts: "norm" in parts or kind == "rmsnorm")
