"""Host Python seconds of compilation before the window: the fits' `trace_s`
(function to jaxpr) plus `lower_s` (jaxpr to MLIR module), cache warm or not.
Where the Pallas call sites show."""
from benchmark import harness

_setup = harness.module("metrics", "setup_program_s.train")


def read(run):
    return _setup.pre_compile(run, "trace_s", "lower_s")
