"""Device time of the state-space recurrence between its projections — the
`while` operations over the layer's row groups (`Mamba2Mixer.apply` maps its
core over groups of rows, each a checkpoint: convolution, decays, the chunked
recurrence with its scan over chunks nested inside, skip, gated norm, the
re-tiling back), forward and backward — as a share of the device time of the
train step program's runs, chip 0.

A loop over row groups is known by what it carries, as the delta rule's is
(`delta_core_share_of_step.train`): an array whose leading axis counts the
groups and which holds, for every row and token of the batch, the layer's x
(heads x head width) or its [B | C] — in any tiling, so the element count is
what is compared. A loop that carries the state [rows, heads, head width,
state width] of the scan over chunks counts too (it is nested in the first
wherever the rows are mapped). A configuration without such layers, or a
program that has no such loop, leaves the metric out."""
import re

from benchmark import harness, trace_reduce

_delta = harness.module("metrics", "delta_core_share_of_step.train")


def widths(run):
    """Per-token widths of the core's x (and its output) and of [B | C], or
    None for a configuration with no state-space layers."""
    c = run.cfg
    if "mamba_num_heads" not in c:
        return None
    return {c["mamba_num_heads"] * c["mamba_head_dim"], 2 * c["n_groups"] * c["ssm_state_size"]}


def state_shape(run):
    c = run.cfg
    return re.compile(rf"(?:f32|bf16)\[\d+,{c['mamba_num_heads']},"
                      rf"{c['mamba_head_dim']},{c['ssm_state_size']}\]")


def core_seconds(run):
    w = widths(run)
    if w is None:
        return None
    batch = run.counters["rows_per_step"] // run.cell["chips"]
    sizes = {batch * run.cfg["input"]["seq_len"] * width for width in w}
    state = state_shape(run)
    ivs = []
    for s, e, name in run.trace.ops[0]:
        if not trace_reduce.short(name).startswith("while"):
            continue
        result = name.split(" while(", 1)[0]
        if _delta.over_row_groups(result, batch, sizes) or state.search(result):
            ivs.append((s, e))
    return trace_reduce.total(trace_reduce.union(ivs)) / 1e9 or None


def read(run):
    _, runs = run.trace.main_module()
    step = sum(e - s for s, e in runs) / 1e9
    core = core_seconds(run)
    if not step or not core:
        return None
    return 100.0 * core / step
