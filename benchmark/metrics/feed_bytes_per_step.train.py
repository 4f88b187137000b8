"""Host bytes handed to the runtime per step of the window (the `bytes=` of
the program's `put` spans): fixed by the traffic, so it repeats exactly."""
from benchmark import span_reduce


def read(run):
    fit, p = span_reduce.phase(run, "put")
    return None if p is None else p["bytes"] / fit["steps"]
