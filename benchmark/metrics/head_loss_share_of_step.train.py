"""Per cent of the train step's device time under `dl4j.loss`, forward and
backward region: the output layer's product and the loss — for integer
labels in row blocks, each block's dz, dx and share of dW made while its
logits are there (part `grad`, PR 45: nothing of the head is recomputed) —
and the penalty (`benchmark/scope_reduce.py`). Left out for a program
without scopes."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, lambda layer, kind, parts: kind == "loss")
