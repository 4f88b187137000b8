"""The KDA chunk rule against its roofline: the least time the chip could
take for the chunked rule's operations and bytes (benchmark/flops:
`kda_flops`, `kda_bytes`; the larger of ops / peak FLOP/s and bytes / peak
B/s — the bytes, with a chunk's decays and scores kept on the chip) over the
device seconds under the mixer's `rule` scope ALONE, chip 0 — the
`dl4j_kda_fwd` + `dl4j_kda_bwd` kernels today, whatever implements the rule
tomorrow. The forward the block's remat runs again is in the seconds and
not in the least work."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.roofline(run, scope_reduce.rule_of("kimideltaattention"),
                                 "kda_flops", "kda_bytes")
