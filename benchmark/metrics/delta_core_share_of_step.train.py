"""Device time of the gated delta rule between its projections — the
`while` operations over the layer's row groups (`GatedDeltaNet.apply` maps
its core over groups of rows, each a checkpoint), forward and backward, the
scans over chunks nested in them included — as a share of the device time of
the train step program's runs, chip 0. `delta_scan_share_of_step.train` is
the inner part.

A loop over row groups is known by what it carries: an array whose leading
axis counts the groups and which holds, for every row and token of the
batch, the [q | k | v] or the value width — [b / rows, rows, t, W] in any
tiling (the program may carry it re-tiled, so the element count is what is
compared, not the trailing axes). A program that does not map its core over
rows has no such loop and the metric is left out."""
import math
import re

from benchmark import trace_reduce

ARRAY = re.compile(r"(?:f32|bf16)\[([0-9,]+)\]")


def widths(run):
    """Per-token widths of the core's input [q | k | v] and output, or None
    for a configuration with no delta-rule layers."""
    c = run.cfg
    if "linear_num_value_heads" not in c:
        return None
    key = c["linear_num_key_heads"] * c["linear_key_head_dim"]
    val = c["linear_num_value_heads"] * c["linear_value_head_dim"]
    return {2 * key + val, val}


def over_row_groups(result, batch, sizes):
    """Does a `while`'s result tuple hold an array [groups, ...] of one of
    `sizes` elements, 1 < groups <= batch a divisor of the batch?"""
    for dims in ARRAY.findall(result):
        dims = [int(d) for d in dims.split(",")]
        if (len(dims) > 2 and 1 < dims[0] <= batch and batch % dims[0] == 0
                and math.prod(dims) in sizes):
            return True
    return False


def core_seconds(run):
    w = widths(run)
    if w is None:
        return None
    batch = run.counters["rows_per_step"] // run.cell["chips"]
    sizes = {batch * run.cfg["input"]["seq_len"] * width for width in w}
    ivs = [(s, e) for s, e, name in run.trace.ops[0]
           if trace_reduce.short(name).startswith("while")
           and over_row_groups(name.split(" while(", 1)[0], batch, sizes)]
    return trace_reduce.total(trace_reduce.union(ivs)) / 1e9 or None


def read(run):
    _, runs = run.trace.main_module()
    step = sum(e - s for s, e in runs) / 1e9
    core = core_seconds(run)
    if not step or not core:
        return None
    return 100.0 * core / step
