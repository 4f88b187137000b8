"""Per step of the window, the wall time of the fit that none of its leaf
phases (etl, put, dispatch, score_wait, listeners) covers: the loop's own
time. More than a millisecond means a boundary has no span."""
from benchmark import span_reduce


def read(run):
    return span_reduce.unspanned_ms(run)
