"""Per step of the window, the time until the jitted step call returned (the
program's `dispatch` span: the rng split, the call, the ambient mesh). The
host's cost of launching a step, not the step."""
from benchmark import span_reduce


def read(run):
    return span_reduce.phase_ms(run, "dispatch")
