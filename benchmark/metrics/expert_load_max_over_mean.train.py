"""Largest over the expert layers of the window's mean per-step
max-over-mean load of the experts held (assignments routed to the busiest
held expert over the mean of the held): the program's device counters, read
once a fit into `telemetry.fit_log()` (`experts`)."""
from benchmark import span_reduce


def experts(run):
    fit = span_reduce.fit_entry(run)
    return (fit or {}).get("experts") or None


def read(run):
    e = experts(run)
    return None if e is None else max(x["load_max_over_mean"] for x in e)
