"""The windowed flash-attention kernels' share of their roofline: the least
time the chip could take for the BANDS' operations and bytes — counted from
the shapes and the window alone, whatever blocks the kernels visit
(benchmark/flops `window_flash_flops` / `window_flash_bytes`, forward +
backward; the larger of ops / peak FLOP/s and bytes / peak B/s) — over the
device time of the `dl4j_flash*` events whose name carries a window, chip 0.
A plan that visits twice the band's scores cannot pass 50 %."""
from benchmark import harness

_share = harness.module("metrics", "window_flash_share_of_step.train")


def read(run):
    _, runs = run.trace.main_module()
    measured = _share.window_seconds(run)
    if not measured or not runs or not hasattr(run.flops, "window_flash_flops"):
        return None
    rows = run.counters["rows_per_step"] // run.cell["chips"]
    least = max(run.flops.window_flash_flops(run.cfg, rows) / run.peaks["bf16_flops_per_s"],
                run.flops.window_flash_bytes(run.cfg, rows) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(runs) / measured
