"""The two readers of the short-convolution mixer on a scope account built by
hand (`scope_reduce.account` over events with the stacks the real step lowers
to): `shortconv_share_of_step.train` takes every part under kind
`gatedshortconv` on both passes, `shortconv_elementwise_share_of_step.train`
its `gates` and `conv` alone (the first minus the second is the two
products); each is None — the metric is left out of the line — where there is
nothing to read (another model, the parent's program, no capture)."""
from types import SimpleNamespace as NS

import pytest

from benchmark import harness, scope_reduce as sr
from benchmark.tests.test_scope_reduce import meta
from deeplearning4j_tpu.telemetry import trace as trace_mod

PARTS = frozenset(trace_mod.SCOPE_PARTS)
BLOCK = "dl4j.L1.sublayerblock"
NAMES = ("shortconv_share_of_step.train", "shortconv_elementwise_share_of_step.train")


def events(kind="gatedshortconv"):
    """Two runs of 1000 ns. Layer 1, a conv mixer: W_in (150), B z (20), the
    taps (30), C c (10), W_out (50); the block's own norm (20); the backward
    region: gates and conv recomputed (15 + 30) and transposed (40 + 60), the
    two products' backward (200); the attention layer's rotation under ITS
    `gates` (50) and a feed-forward (100); 225 unscoped."""
    fwd = f"jit(step)/jvp({BLOCK})/dl4j.{kind}/"
    bwd = f"jit(step)/transpose(jvp({BLOCK}))/dl4j.{kind}/"
    re = f"jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/{BLOCK}/dl4j.{kind}/"
    md = {"%proj.1": meta(fwd + "proj/dot_general:"),
          "%gate.2": meta(fwd + "gates/mul:"),
          "%conv.3": meta(fwd + "conv/add:"),
          "%gate.4": meta(fwd + "gates/mul:"),
          "%out.5": meta(fwd + "out/dot_general:"),
          "%norm.6": meta(f"jit(step)/jvp({BLOCK})/norm/mul:"),
          "%gate.7": meta(re + "gates/mul:"),
          "%conv.8": meta(re + "conv/add:"),
          "%gate.9": meta(bwd + "gates/mul:"),
          "%conv.10": meta(bwd + "conv/add:"),
          "%proj.11": meta(bwd + "proj/dot_general:"),
          "%rot.12": meta("jit(step)/jvp(dl4j.L3.sublayerblock)/dl4j.gatedattention/gates/mul:"),
          "%mlp.13": meta("jit(step)/jvp(dl4j.L2.sublayerblock)/dl4j.gatedmlp/mlp/dot_general:"),
          "%copy.14": meta("jit(step)/copy:", "data formatting")}
    ops = []
    for t0 in (0, 5000):
        at = t0
        for name, ns in (("%proj.1", 150), ("%gate.2", 20), ("%conv.3", 30), ("%gate.4", 10),
                         ("%out.5", 50), ("%norm.6", 20), ("%gate.7", 15), ("%conv.8", 30),
                         ("%gate.9", 40), ("%conv.10", 60), ("%proj.11", 200), ("%rot.12", 50),
                         ("%mlp.13", 100), ("%copy.14", 225)):
            ops.append((at, at + ns, name))
            at += ns
    return ops, md


def run_with(monkeypatch, acct):
    monkeypatch.setattr(sr, "scope_account", lambda run: acct)
    return NS(cell={"name": "cell", "chips": 1}, trace=None)


def read(name, run):
    return harness.module("metrics", name).read(run)


def test_both_readers_on_a_conv_mixer(monkeypatch):
    ops, md = events()
    acct = sr.account(ops, "jit_step(7)", [(0, 1000), (5000, 6000)], md, PARTS)
    ns = {k: [round(v * 1e9) for v in r[:3]] for k, r in acct.rows.items()}
    assert ns[("1", "gatedshortconv", ("conv",))] == [60, 180, 60]    # forward, backward, recompute
    assert ns[("1", "gatedshortconv", ("gates",))] == [60, 110, 30]
    run = run_with(monkeypatch, acct)
    # 150 + 20 + 30 + 10 + 50 forward, 15 + 30 + 40 + 60 + 200 in the backward region, of 1000
    assert read(NAMES[0], run) == pytest.approx(60.5)
    # gates + conv alone: 60 forward, 145 behind; the attention layer's `gates` is not the mixer's
    assert read(NAMES[1], run) == pytest.approx(20.5)
    assert read(NAMES[0], run) - read(NAMES[1], run) == pytest.approx(40.0)   # the two products


def test_another_mixer_is_not_read(monkeypatch):
    """The same parts under a recurrent mixer's kind (its `conv` and `gates`
    are `mixer_around_rule_share_of_step.train`'s): neither reader takes them."""
    ops, md = events(kind="gateddeltanet")
    acct = sr.account(ops, "jit_step(7)", [(0, 1000), (5000, 6000)], md, PARTS)
    for name in NAMES:
        assert read(name, run_with(monkeypatch, acct)) is None, name


def test_nothing_to_read_is_none_not_an_error(monkeypatch, tmp_path):
    for name in NAMES:
        assert read(name, run_with(monkeypatch, None)) is None, name        # no account at all
    monkeypatch.undo()
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))                # no capture
    monkeypatch.setattr(sr, "_cache", {})
    for name in NAMES:
        assert read(name, NS(cell={"name": "cell", "chips": 1}, trace=None)) is None


def test_benchmark_json_lists_them_for_the_one_cell():
    import json
    import os

    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        m = by_name[name]
        assert m["workloads"] == ["lfm2_train_t8192"]
        assert (m["layer"], m["moves"], m["source"], m["unit"], m["better"]) == (
            "kernels", "train_throughput", "device_trace", "%", "lower")
    cell = harness.load_cell("lfm2_train_t8192")
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "lfm2-24b-a2b-l5", "train_ids_t8192_b2")
    listed = {m["name"] for m in cell["per_layer"]}
    assert set(NAMES) <= listed
    assert {"flash_roofline.train", "mfu.train", "step_scoped_share.train",
            "expert_share_of_step.train", "expert_product_share_of_step.train",
            "expert_load_max_over_mean.train", "expert_dropped_assignments.train",
            "expert_capacity_fill.train"} <= listed
    # and no reader of another mixer's parts
    assert not {"rope_share_of_step.train", "latent_attention_share_of_step.train",
                "mixer_rule_share_of_step.train", "kda_share_of_step.train",
                "ssd_share_of_step.train", "delta_core_share_of_step.train"} & listed
