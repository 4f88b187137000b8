"""Device time of the gated delta rule's scans over chunks — the `while`
operations whose carried tuple holds the matrix state
[rows, value heads, key dim, value dim], forward and backward — as a share
of the device time of the train step program's runs, chip 0. Rows: as many
as the layer takes at a time (1 of the batch's 2 at the cell's size). Any
dtype: the program carries float32, but every use of the state is a product
on bf16 operands and XLA then keeps the carry itself in bf16."""
import re

from benchmark import trace_reduce


def state_shape(run):
    """The state's shape as the trace writes it (any number of rows), or
    None for a configuration with no delta-rule layers."""
    c = run.cfg
    if "linear_num_value_heads" not in c:
        return None
    return re.compile(rf"(?:f32|bf16)\[\d+,{c['linear_num_value_heads']},"
                      rf"{c['linear_key_head_dim']},{c['linear_value_head_dim']}\]")


def scan_seconds(run):
    shape = state_shape(run)
    if shape is None:
        return None
    ivs = [(s, e) for s, e, name in run.trace.ops[0]
           if trace_reduce.short(name).startswith("while")
           and shape.search(name.split(" while(", 1)[0])]
    return trace_reduce.total(trace_reduce.union(ivs)) / 1e9 or None


def read(run):
    _, runs = run.trace.main_module()
    step = sum(e - s for s, e in runs) / 1e9
    scan = scan_seconds(run)
    if not step or not scan:
        return None
    return 100.0 * scan / step
