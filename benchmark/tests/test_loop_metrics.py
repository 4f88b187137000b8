"""The four readers of the looped stack. `loop_share_of_step.train` and
`norm_share_of_step.train` on a scope account built by hand
(`scope_reduce.account` over events with the stacks the real step lowers to:
a nested block's own layer scope INSIDE the stack's); `exit_expected_passes
.train` and `exit_entropy.train` on a recorded `fit_log()` entry. Each is None
— the metric is left out of the line — where there is nothing to read
(another model, the parent's program, no capture)."""
import json
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import harness, scope_reduce as sr, span_reduce
from benchmark.tests.test_scope_reduce import meta
from deeplearning4j_tpu.telemetry import trace as trace_mod

PARTS = frozenset(trace_mod.SCOPE_PARTS)
STACK = "dl4j.L1.loopedstack"
TRACED = ("loop_share_of_step.train", "norm_share_of_step.train")
COUNTED = ("exit_expected_passes.train", "exit_entropy.train")
CELL = "ouro_train_t8192_b1"


def events(stack=STACK):
    """Two runs of 1000 ns. The embedding (30); in the stack, nested block 0:
    its norm (20), the attention's products and kernel (150), its second norm
    (20); nested block 1: norm (20), feed-forward (130), norm (20); the final
    norm, a nested layer of its own kind (10); the stack's own stacking of the
    passes (10); the loss: four heads (150) and the exit gate (10); the
    backward region of block 0's attention (200) and its norm recomputed (15);
    the update (100); 115 unscoped."""
    def nested(j, kind):
        return f"jit(step)/jvp({stack})/dl4j.L{j}.{kind}/"
    bwd = f"jit(step)/transpose(jvp({stack}))/dl4j.L0.sublayerblock/"
    re = (f"jit(step)/transpose(jvp({stack}))/checkpoint/rematted_computation/"
          "dl4j.L0.sublayerblock/")
    md = {"%emb.1": meta("jit(step)/jvp(dl4j.L0.embeddingsequence)/gather:"),
          "%n.2": meta(nested(0, "sublayerblock") + "norm/mul:"),
          "%att.3": meta(nested(0, "sublayerblock") + "dl4j.gatedattention/attend/custom_call:"),
          "%n.4": meta(nested(0, "sublayerblock") + "norm/mul:"),
          "%n.5": meta(nested(1, "sublayerblock") + "norm/mul:"),
          "%mlp.6": meta(nested(1, "sublayerblock") + "mlp/dot_general:"),
          "%n.7": meta(nested(1, "sublayerblock") + "norm/mul:"),
          "%fn.8": meta(nested(2, "rmsnorm") + "mul:"),
          "%stack.9": meta(f"jit(step)/jvp({stack})/concatenate:"),
          "%head.10": meta("jit(step)/jvp(dl4j.loss)/while/body/dot_general:"),
          "%exit.11": meta("jit(step)/jvp(dl4j.loss)/exit/mul:"),
          "%att.12": meta(bwd + "dl4j.gatedattention/attend/custom_call:"),
          "%n.13": meta(re + "norm/mul:"),
          "%adam.14": meta("jit(step)/dl4j.update/mul:"),
          "%copy.15": meta("jit(step)/copy:", "data formatting")}
    ops = []
    for t0 in (0, 5000):
        at = t0
        for name, ns in (("%emb.1", 30), ("%n.2", 20), ("%att.3", 150), ("%n.4", 20), ("%n.5", 20),
                         ("%mlp.6", 130), ("%n.7", 20), ("%fn.8", 10), ("%stack.9", 10),
                         ("%head.10", 150), ("%exit.11", 10), ("%att.12", 200), ("%n.13", 15),
                         ("%adam.14", 100), ("%copy.15", 115)):
            ops.append((at, at + ns, name))
            at += ns
    return ops, md


RUNS = [(0, 1000), (5000, 6000)]


def traced_run(monkeypatch, ops, md):
    """A run whose capture holds `ops` with the metadata `md`."""
    acct = sr.account(ops, "jit_step(7)", RUNS, md, PARTS)
    monkeypatch.setattr(sr, "scope_account", lambda run: acct)
    monkeypatch.setattr(sr, "capture_file", lambda cell: "a.xplane.pb")
    monkeypatch.setattr(sr, "op_metadata", lambda path: md)
    monkeypatch.setattr(sr, "part_words", lambda: PARTS)
    trace = NS(ops={0: ops}, main_module=lambda chip=0: ("jit_step(7)", RUNS))
    return NS(cell={"name": "cell", "chips": 1}, trace=trace), acct


def read(name, run):
    return harness.module("metrics", name).read(run)


def test_the_loop_and_the_norms_on_a_looped_step(monkeypatch):
    ops, md = events()
    run, acct = traced_run(monkeypatch, ops, md)
    # a nested block is named by ITS layer scope: the stack's own kind holds
    # only what the stack did itself
    ns = {k: [round(v * 1e9) for v in r[:3]] for k, r in acct.rows.items()}
    assert ns[("1", "loopedstack", ())] == [20, 0, 0]
    assert ns[("0", "gatedattention", ("attend",))] == [300, 400, 0]
    assert ns[("0", "sublayerblock", ("norm",))] == [80, 30, 30]
    # everything under the stack: 20 + 150 + 20 + 20 + 130 + 20 + 10 + 10 forward,
    # 200 + 15 in the backward region, of 1000
    assert read(TRACED[0], run) == pytest.approx(59.5)
    # the six norm passes: 4 x 20 + 10 + 15 recomputed
    assert read(TRACED[1], run) == pytest.approx(10.5)


def test_a_flat_stack_has_no_loop_but_has_norms(monkeypatch):
    """The same blocks as network layers of a flat model (no stack around
    them): no loop to read; the blocks' norms are read all the same."""
    ops, md = events(stack="dl4j.L1.sublayerblock")
    run, _ = traced_run(monkeypatch, ops, md)
    assert read(TRACED[0], run) is None
    assert read(TRACED[1], run) == pytest.approx(10.5)


def test_nothing_to_read_is_none_not_an_error(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))                # no capture
    monkeypatch.setattr(sr, "_cache", {})
    bare = NS(cell={"name": "cell", "chips": 1}, trace=None, counters={})
    for name in TRACED + COUNTED:
        assert read(name, bare) is None, name
    monkeypatch.setattr(sr, "program_has_seam", lambda: False)              # the parent of PR 35
    for name in TRACED:
        assert read(name, bare) is None, name


def test_the_exit_counters_are_read_from_the_windows_fit(monkeypatch):
    exit_ = {"layer": "layer_2", "steps": 21, "exit_p": [0.5, 0.25, 0.125, 0.125],
             "expected_passes": 1.875, "exit_entropy": 1.2130, "loss_by_pass": [10.9] * 4}
    monkeypatch.setattr(span_reduce, "fit_entry", lambda run: {"steps": 21, "exit": [exit_]})
    assert read(COUNTED[0], NS()) == 1.875
    assert read(COUNTED[1], NS()) == 1.2130
    # a model that keeps no such counter, a window no fit matches
    monkeypatch.setattr(span_reduce, "fit_entry", lambda run: {"steps": 21, "experts": [{}]})
    assert [read(n, NS()) for n in COUNTED] == [None, None]
    monkeypatch.setattr(span_reduce, "fit_entry", lambda run: None)
    assert [read(n, NS()) for n in COUNTED] == [None, None]


def test_benchmark_json_lists_them_for_the_one_cell():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert set(TRACED + COUNTED) <= set(by_name)
    for name in TRACED:
        m = by_name[name]
        assert (m["workloads"], m["layer"], m["moves"], m["source"], m["unit"], m["better"]) == (
            [CELL], "kernels", "train_throughput", "device_trace", "%", "lower")
    for name, unit, better in zip(COUNTED, ("ratio", "nats"), ("lower", "higher")):
        m = by_name[name]
        assert (m["workloads"], m["layer"], m["moves"], m["source"], m["unit"], m["better"]) == (
            [CELL], "looped stack", "train_throughput", "program_counter", unit, better)
    cell = harness.load_cell(CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "ouro-2.6b-l6", "train_ids_t8192_b1")
    listed = {m["name"] for m in cell["per_layer"]}
    assert set(TRACED + COUNTED) <= listed
    assert {"flash_roofline.train", "mfu.train", "step_scoped_share.train",
            "flash_share_of_step.train", "head_loss_share_of_step.train",
            "forward_share_of_step.train"} <= listed
    # and no reader of the experts or of a recurrent mixer
    assert not any(n.startswith(("expert_", "mixer_", "delta_", "kda_", "ssd_", "shortconv_",
                                 "rope_", "latent_")) for n in listed)
    assert [m["name"] for m in cell["end_to_end"]] == ["train_throughput", "setup_s"]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers"]
