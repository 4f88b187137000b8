"""Per cent of the train step's device time the state-space mixer spends
BETWEEN its projections, both passes and what is recomputed: every part
under the program's `dl4j.mamba2mixer` scope but `proj` — the convolution,
the decays, the chunked recurrence (the `dl4j_ssd_*` kernels:
`mixer_rule_share_of_step.train`), skip, gated norm, the re-tiling, the
counters, and the row loops' own slicing (the rows with no part). Found by
the program's names (`benchmark/scope_reduce.py`), as
`delta_core_share_of_step.train` is: no loop's shape is matched. Left out
where no such mixer ran under a scope."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, scope_reduce.between_projections("mamba2mixer"))
