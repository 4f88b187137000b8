"""Per cent of the train step's device time under `dl4j.gatedshortconv`,
every part, both passes: the two products (`proj`, `out`) and the three
elementwise passes between them (`gates`, `conv`) of every short-convolution
mixer, their recompute and their backward — the new mechanism whole. Left out
where no such mixer ran under a scope."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, lambda layer, kind, parts: kind == "gatedshortconv")
