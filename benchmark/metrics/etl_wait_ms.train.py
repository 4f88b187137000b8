"""Per step of the window, the time the fit thread waited for its next batch
(the program's `etl` span, open while the iterator's `next()` runs), from the
program's own account of that fit."""
from benchmark import span_reduce


def read(run):
    return span_reduce.phase_ms(run, "etl")
