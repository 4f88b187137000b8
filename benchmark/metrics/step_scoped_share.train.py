"""Per cent of the train step's device time (the main program's runs, chip
0) spent in operations under ANY of the program's device scopes
(`dl4j.L<i>.<kind>`, `dl4j.loss`, `dl4j.update`; `benchmark/scope_reduce.py`).
What is left is operations the compiler made with no scoped ancestor
(parameter copies, the step's scalar tail) and time inside a run with no
operation open. 0 — with one printed line — when the program opens scopes
and the trace holds none: the step's executable predates them (the compile
cache's key strips names). Left out for a program without scopes."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run)
