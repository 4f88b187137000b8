"""Device time of the per-channel-gated delta rule (KDA) between its
projections — the `while` operations over the layer's row groups
(`KimiDeltaAttention.apply` maps its core over groups of rows, each a
checkpoint: the three short convolutions, the decays, the chunk rule with its
solve and its scan over chunks nested inside, the gated norm, the re-tiling
back), forward and backward — as a share of the device time of the train step
program's runs, chip 0.

A loop over row groups is known by what it carries, as the scalar delta
rule's and the state-space core's are (`delta_core_share_of_step.train`): an
array whose leading axis counts the groups and which holds, for every row and
token of the batch, the layer's [q | k | v] or one head-channel array (the
decay, the gate, the output) — in any tiling, so the element count is what is
compared. A loop that carries the state [rows, heads, key width, value width]
of the scan over chunks counts too (it is nested in the first wherever the
rows are mapped). A configuration without such layers, or a program that has
no such loop, leaves the metric out."""
import re

from benchmark import harness, trace_reduce

_delta = harness.module("metrics", "delta_core_share_of_step.train")


def dims(run):
    """(heads, head width) of the KDA layers, or None for a configuration
    that has none."""
    lin = run.cfg.get("linear_attn_config")
    if not lin or "kda_layers" not in lin:
        return None
    return lin["num_heads"], lin["head_dim"]


def core_seconds(run):
    hd = dims(run)
    if hd is None:
        return None
    h, d = hd
    batch = run.counters["rows_per_step"] // run.cell["chips"]
    sizes = {batch * run.cfg["input"]["seq_len"] * width for width in (3 * h * d, h * d)}
    state = re.compile(rf"(?:f32|bf16)\[\d+,{h},{d},{d}\]")
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
