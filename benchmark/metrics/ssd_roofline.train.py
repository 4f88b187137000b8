"""The state-space chunk rule against its roofline: the least time the chip
could take for the chunked recurrence's operations and bytes
(benchmark/flops: `ssd_flops`, `ssd_bytes`; the larger of ops / peak FLOP/s
and bytes / peak B/s — the bytes, with a chunk's decays and scores kept on
the chip) over the device seconds under the mixer's `rule` scope ALONE,
chip 0 — the `dl4j_ssd_fwd` + `dl4j_ssd_bwd` kernels and the small XLA split
in front of them today, whatever implements the rule tomorrow. The forward
the block's remat runs again is in the seconds and not in the least work."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.roofline(run, scope_reduce.rule_of("mamba2mixer"),
                                 "ssd_flops", "ssd_bytes")
