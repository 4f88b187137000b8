"""Median request latency from the due time, over all requests of the
traced window (see serve_p95_ms.serve)."""


def read(run):
    return run.counters["summary"]["serve_p50_ms"]
