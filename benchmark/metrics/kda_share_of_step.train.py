"""Per cent of the train step's device time the per-channel-gated delta
rule (KDA) spends BETWEEN its projections, both passes and what is
recomputed: every part under the program's `dl4j.kimideltaattention` scope
but `proj` — the three short convolutions, the decays and gates, the chunk
rule (the `dl4j_kda_*` kernels: `mixer_rule_share_of_step.train`), the head
norm and gate, the re-tiling, the counters, and the row loops' own slicing
(the rows with no part). Found by the program's names
(`benchmark/scope_reduce.py`), as `delta_core_share_of_step.train` is: no
loop's shape is matched. Left out where no such mixer ran under a scope."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, scope_reduce.between_projections("kimideltaattention"))
