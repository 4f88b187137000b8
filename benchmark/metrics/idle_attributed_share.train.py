"""Per cent of chip 0's idle time, in the host capture, during which some
`dl4j.*` span was open on the fit thread: how much of the idle time the
program's own spans can name."""
from benchmark import span_reduce


def read(run):
    return span_reduce.idle_share(run)
