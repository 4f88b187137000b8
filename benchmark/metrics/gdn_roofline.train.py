"""The gated delta rule's chunks against their roofline: the least time the
chip could take for the chunk rule's operations and bytes with a chunk kept
on the chip (benchmark/flops: `gdn_flops`, `gdn_bytes`; the larger of ops /
peak FLOP/s and bytes / peak B/s — the bytes) over the device seconds under
the mixer's `rule` scope, chip 0 — the `dl4j_gdn_fwd` + `dl4j_gdn_bwd`
kernels since PR 37, whatever implements the rule tomorrow. The forward the
block's remat runs again is in the seconds and not in the least work."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.roofline(run, scope_reduce.rule_of("gateddeltanet"),
                                 "gdn_flops", "gdn_bytes")
