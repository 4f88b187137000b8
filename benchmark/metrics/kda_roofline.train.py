"""The KDA core against the roofline of its recurrence: the least time the
chip could take for the chunked rule's operations and bytes (benchmark/flops:
`kda_flops`, `kda_bytes`; the larger of ops / peak FLOP/s and bytes / peak
B/s — the bytes, with a chunk's decays and scores kept on the chip) over the
device time of the core's `while` operations on chip 0
(`kda_share_of_step.train`: the convolutions, the gate and the norm run inside
them and are in the time, not in the least work)."""
from benchmark import harness

_share = harness.module("metrics", "kda_share_of_step.train")


def read(run):
    _, runs = run.trace.main_module()
    if not runs or not hasattr(run.flops, "kda_flops"):
        return None
    measured = _share.core_seconds(run)
    if not measured:
        return None
    rows = run.counters["rows_per_step"] // run.cell["chips"]
    least = max(run.flops.kda_flops(run.cfg, rows) / run.peaks["bf16_flops_per_s"],
                run.flops.kda_bytes(run.cfg, rows) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(runs) / measured
