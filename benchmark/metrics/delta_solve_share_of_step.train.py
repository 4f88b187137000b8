"""Per cent of the train step's device time under `rule/solve`, both
passes: the chunks' unit-lower-triangular solve of the XLA delta rules
(`hybrid._solve_writes`) and its backward. Left out where no `solve` ran
under a scope (the KDA kernels solve inside `dl4j_kda_*`)."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, lambda layer, kind, parts: parts[:2] == ("rule", "solve"))
