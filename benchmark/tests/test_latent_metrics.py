"""The two readers of the latent-attention layer on a scope account built by
hand (`scope_reduce.account` over events with the stacks the real step
lowers to): `latent_attention_share_of_step.train` takes every part under
kind `latentattention` on both passes, `rope_share_of_step.train` the part
`rope` under it alone; each is None — the metric is left out of the line —
where there is nothing to read (a layer that knows no positions, a program
whose seam lacks the word, another model, no capture)."""
from types import SimpleNamespace as NS

import pytest

from benchmark import harness, scope_reduce as sr
from benchmark.tests.test_scope_reduce import meta

PARTS = frozenset({"proj", "norm", "rope", "attend", "out", "mlp", "rule", "product"})
BLOCK = "dl4j.L1.sublayerblock"


def events(rope=True):
    """Two runs of 1000 ns. Layer 1: a projection (100), the rotation (30),
    the flash forward (200), the output product (50); the block's own norm
    (20); the backward region: the rotation recomputed (30) and transposed
    (40), the flash backward (300); a feed-forward (100); 130 unscoped."""
    fwd = f"jit(step)/jvp({BLOCK})/dl4j.latentattention/"
    bwd = f"jit(step)/transpose(jvp({BLOCK}))/dl4j.latentattention/"
    re = (f"jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/{BLOCK}/"
          "dl4j.latentattention/")
    part = "rope" if rope else "proj"
    md = {"%proj.1": meta(fwd + "proj/dot_general:"),
          "%rope.2": meta(fwd + part + "/mul:"),
          "%flash.3": meta(fwd + "attend/pallas_call:"),
          "%out.4": meta(fwd + "out/dot_general:"),
          "%norm.5": meta(f"jit(step)/jvp({BLOCK})/norm/mul:"),
          "%rope.6": meta(re + part + "/mul:"),
          "%rope.7": meta(bwd + part + "/mul:"),
          "%flash.8": meta(bwd + "attend/pallas_call:"),
          "%mlp.9": meta("jit(step)/jvp(dl4j.L2.sublayerblock)/dl4j.gatedmlp/mlp/dot_general:"),
          "%copy.10": meta("jit(step)/copy:", "data formatting")}
    ops = []
    for t0 in (0, 5000):
        at = t0
        for name, ns in (("%proj.1", 100), ("%rope.2", 30), ("%flash.3", 200), ("%out.4", 50),
                         ("%norm.5", 20), ("%rope.6", 30), ("%rope.7", 40), ("%flash.8", 300),
                         ("%mlp.9", 100), ("%copy.10", 130)):
            ops.append((at, at + ns, name))
            at += ns
    return ops, md


def run_with(monkeypatch, acct):
    monkeypatch.setattr(sr, "scope_account", lambda run: acct)
    return NS(cell={"name": "cell", "chips": 1}, trace=None)


def read(name, run):
    return harness.module("metrics", name).read(run)


def test_both_readers_on_a_rotating_layer(monkeypatch):
    ops, md = events()
    acct = sr.account(ops, "jit_step(7)", [(0, 1000), (5000, 6000)], md, PARTS)
    ns = {k: [round(v * 1e9) for v in r[:3]] for k, r in acct.rows.items()}
    assert ns[("1", "latentattention", ("rope",))] == [60, 140, 60]     # forward, backward, recompute
    run = run_with(monkeypatch, acct)
    # 100 + 30 + 200 + 50 forward, 30 + 40 + 300 in the backward region, of 1000 ns a run
    assert read("latent_attention_share_of_step.train", run) == pytest.approx(75.0)
    assert read("rope_share_of_step.train", run) == pytest.approx(10.0)
    # the block's own norm and the feed-forward are not the layer's
    assert acct.seconds(lambda l, kind, parts: kind == "sublayerblock") == pytest.approx(40e-9)


def test_a_layer_without_positions_has_no_rope_to_read(monkeypatch):
    """The `kimi_linear` shape: the same layer, no rotation."""
    ops, md = events(rope=False)
    acct = sr.account(ops, "jit_step(7)", [(0, 1000), (5000, 6000)], md, PARTS)
    run = run_with(monkeypatch, acct)
    assert read("latent_attention_share_of_step.train", run) == pytest.approx(75.0)
    assert read("rope_share_of_step.train", run) is None


def test_a_seam_without_the_word_reads_no_rope(monkeypatch):
    """The parent's `SCOPE_PARTS` has no `rope`: a stack that held the word
    would be booked to the layer with no part, and the reader finds nothing."""
    ops, md = events()
    acct = sr.account(ops, "jit_step(7)", [(0, 1000), (5000, 6000)], md, PARTS - {"rope"})
    run = run_with(monkeypatch, acct)
    assert read("rope_share_of_step.train", run) is None
    assert read("latent_attention_share_of_step.train", run) == pytest.approx(75.0)


def test_nothing_to_read_is_none_not_an_error(monkeypatch, tmp_path):
    other = {"%mlp.9": meta("jit(step)/jvp(dl4j.L2.sublayerblock)/dl4j.gatedmlp/mlp/dot_general:")}
    acct = sr.account([(0, 100, "%mlp.9")], "jit_step(7)", [(0, 100)], other, PARTS)
    for name in ("latent_attention_share_of_step.train", "rope_share_of_step.train"):
        assert read(name, run_with(monkeypatch, acct)) is None, name        # another model
        assert read(name, run_with(monkeypatch, None)) is None, name        # no account at all
    monkeypatch.undo()
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))                # no capture
    monkeypatch.setattr(sr, "_cache", {})
    for name in ("latent_attention_share_of_step.train", "rope_share_of_step.train"):
        assert read(name, NS(cell={"name": "cell", "chips": 1}, trace=None)) is None


def test_benchmark_json_lists_them_where_they_read():
    import json
    import os

    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    latent, rope = (by_name[n] for n in ("latent_attention_share_of_step.train",
                                         "rope_share_of_step.train"))
    assert latent["workloads"] == ["kanana2_train_t8192", "kimilinear_train_t8192"]
    assert rope["workloads"] == ["kanana2_train_t8192"]
    for m in (latent, rope):
        assert (m["layer"], m["moves"], m["source"], m["unit"]) == (
            "kernels", "train_throughput", "device_trace", "%")
    for cell in latent["workloads"]:
        assert latent["name"] in {m["name"] for m in harness.load_cell(cell)["per_layer"]}
    assert rope["name"] in {m["name"] for m in harness.load_cell("kanana2_train_t8192")["per_layer"]}
