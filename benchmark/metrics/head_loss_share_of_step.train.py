"""Per cent of the train step's device time under `dl4j.loss`, forward and
backward region: the output layer's product, the loss (in row blocks for
integer labels, each recomputed in its backward) and the penalty
(`benchmark/scope_reduce.py`). Left out for a program without scopes."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, lambda layer, kind, parts: kind == "loss")
