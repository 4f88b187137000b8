"""Per step of the window, the time the fit thread blocked reading the loss
back (`float(score)`; the program's `score_wait` span): the device's step
plus whatever of the feed the runtime had not finished."""
from benchmark import span_reduce


def read(run):
    return span_reduce.phase_ms(run, "score_wait")
