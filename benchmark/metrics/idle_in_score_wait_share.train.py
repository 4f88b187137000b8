"""Per cent of chip 0's idle time, in the host capture, during which the fit
thread sat in `dl4j.score_wait` (innermost): the chip idle while the host
only waits for it. An overlapped feed takes it toward 0."""
from benchmark import span_reduce


def read(run):
    return span_reduce.idle_share(run, "dl4j.score_wait")
