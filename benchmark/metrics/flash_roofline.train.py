"""The flash-attention kernels' share of their roofline: the least time the
chip could take for their operations and bytes (benchmark/flops; the larger
of ops / peak FLOP/s and bytes / peak B/s — at t = 1024, head 64 it is the
compute side) over the device time of the `dl4j_flash_*` events on chip 0."""


def read(run):
    measured = run.trace.kernel_seconds("dl4j_flash")
    _, runs = run.trace.main_module()
    if not measured or not runs or not hasattr(run.flops, "flash_flops"):
        return None
    rows = run.counters["rows_per_step"] // run.cell["chips"]
    least = max(run.flops.flash_flops(run.cfg, rows) / run.peaks["bf16_flops_per_s"],
                run.flops.flash_bytes(run.cfg, rows) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(runs) / measured
