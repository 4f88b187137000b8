"""Fullest expert layer's share of its sorted buffer that held real
assignments over the window, per cent (`telemetry.fit_log()`, `experts`):
the rest is padding the grouped product computes all the same."""
from benchmark import harness

_load = harness.module("metrics", "expert_load_max_over_mean.train")


def read(run):
    e = _load.experts(run)
    return None if e is None else 100.0 * max(x["capacity_fill"] for x in e)
