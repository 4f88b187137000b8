"""Device time of the WINDOWED flash-attention kernels — the `dl4j_flash*`
events whose name carries a window (`.._d128_w512_..`: the sliding layers'
forward and backward) — as a share of the device time of the train step
program's runs, chip 0. `flash_share_of_step.train` reads these and the
global layers' together. Left out where no such kernel ran (a program
without a window in its kernels, a step without a windowed layer)."""
import re

from benchmark import trace_reduce

WINDOWED = re.compile(r"dl4j_flash[a-z_]*_bh\d+_t\d+_d\d+(?:_dv\d+)?_w\d+_")


def window_seconds(run):
    """Device seconds of the windowed flash kernels on chip 0."""
    return trace_reduce.total(trace_reduce.union(
        [(s, e) for s, e, n in run.trace.ops[0]
         if WINDOWED.search(trace_reduce.short(n))])) / 1e9


def read(run):
    _, runs = run.trace.main_module()
    step = sum(e - s for s, e in runs) / 1e9
    banded = window_seconds(run)
    if not step or not banded:
        return None
    return 100.0 * banded / step
