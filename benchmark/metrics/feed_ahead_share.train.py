"""Per cent of the window's steps whose inputs the fit thread handed to the
runtime BEFORE it read the previous step's score (the `staged_ahead` count
of the program's `fit_log()` entry over its `steps`): how often the fit
loop's one-batch look-ahead engaged. All of a fit's steps but its first can
be ahead, so a window of n steps reads 100 (n - 1) / n. None for a program
whose fit log has no such count."""
from benchmark import span_reduce


def read(run):
    fit = span_reduce.fit_entry(run)
    if fit is None or "staged_ahead" not in fit:
        return None
    return 100.0 * fit["staged_ahead"] / fit["steps"]
