"""The delta rule's scans over chunks against their roofline: the least
time the chip could take for their operations and bytes (benchmark/flops:
`delta_scan_flops`, `delta_scan_bytes`; the larger of ops / peak FLOP/s and
bytes / peak B/s — the bytes, with the state kept on the chip) over the
device time of those `while` operations on chip 0."""
from benchmark import harness

_share = harness.module("metrics", "delta_scan_share_of_step.train")


def read(run):
    _, runs = run.trace.main_module()
    measured = _share.scan_seconds(run)
    if not measured or not runs or not hasattr(run.flops, "delta_scan_flops"):
        return None
    rows = run.counters["rows_per_step"] // run.cell["chips"]
    least = max(run.flops.delta_scan_flops(run.cfg, rows) / run.peaks["bf16_flops_per_s"],
                run.flops.delta_scan_bytes(run.cfg, rows) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(runs) / measured
