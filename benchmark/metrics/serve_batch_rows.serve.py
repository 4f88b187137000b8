"""Rows answered per batch the server dispatched (the benchmark counts the
calls of the server's dispatch function; the server keeps no such count)."""


def read(run):
    b = run.counters.get("batches")
    return run.counters["rows_answered"] / b if b else None
