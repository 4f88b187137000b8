"""Per cent of the train step's device time the gated delta rule spends
BETWEEN its projections, both passes and what is recomputed: every part
under the program's `dl4j.gateddeltanet` scope but `proj` — the short
convolution, the gates, the chunk rule (`mixer_rule_share_of_step.train`),
the norm and gate, the re-tiling in and out, the counters, and the row
loops' own slicing while the core is mapped over groups of rows (the rows
with no part). Found by the names the program gives its work
(`benchmark/scope_reduce.py`), not by the shape of a loop: a step that runs
the core for all rows at once, or as one jitted function, reads the same
work. Left out where no such mixer ran under a scope."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, scope_reduce.between_projections("gateddeltanet"))
