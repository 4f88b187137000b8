"""Assignments the expert layers dropped in the window because their
sorted buffer was full, summed over the layers (`telemetry.fit_log()`,
`experts`). The reference drops nothing: `correct` needs 0."""
from benchmark import harness

_load = harness.module("metrics", "expert_load_max_over_mean.train")


def read(run):
    e = _load.experts(run)
    return None if e is None else sum(x["dropped_assignments"] for x in e)
