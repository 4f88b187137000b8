"""The four readers of the windowed attention layers: the two kernel readers
on a synthetic trace (flash events with and without a window in their name),
`attention_share_of_step.train` on a scope account built by hand,
`window_band_fill.train` on a `fit_log()` entry; each is None — the metric is
left out of the line — where there is nothing to read (the parent's program,
another model, no capture)."""
from types import SimpleNamespace as NS

import pytest

from benchmark import harness, scope_reduce as sr, span_reduce, trace_reduce as tr
from benchmark.tests.test_hybrid_metrics import config, read
from benchmark.tests.test_scope_reduce import meta
from benchmark.tests.test_trace_reduce import ev
from deeplearning4j_tpu.telemetry import trace as trace_mod

PARTS = frozenset(trace_mod.SCOPE_PARTS)
NAMES = ("window_flash_roofline.train", "window_flash_share_of_step.train",
         "attention_share_of_step.train", "window_band_fill.train")
BANDED = "dl4j_flash_{}_bh36_t8192_d128_w512_bq512_bk512_bfloat16"
WHOLE = "dl4j_flash_{}_bh24_t8192_d128_bq512_bk512_bfloat16"


def planes(banded=True):
    """Two runs of the step, 100 ms each: the global layers' kernels (2 + 6
    ms), the sliding layers' (3 x (2 + 4) ms, their names carry the window),
    a rope kernel whose name has a `_r128_` (no window) and a fusion."""
    ops, mods = [], []
    for base in (0, 500_000_000):
        mods.append(ev("jit_step(7)", base, 100_000_000))
        at = base
        events = [(f"%{WHOLE.format('fwd')}.1 = bf16[24,8192,128] custom-call()", 2_000_000),
                  (f"%{WHOLE.format('bwd')}.2 = bf16[24,8192,128] custom-call()", 6_000_000),
                  ("%dl4j_rope_fwd_bh44_t8192_d128_r128_bfloat16.3 = bf16[1,36,8192,128] "
                   "custom-call()", 1_000_000),
                  ("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 50_000_000)]
        if banded:
            for i in range(3):
                events += [(f"%{BANDED.format('fwd')}.{10 + i} = bf16[36,8192,128] custom-call()",
                            2_000_000),
                           (f"%closed_call.{i}/{BANDED.format('bwd')}.{20 + i} = bf16[36,8192,128] "
                            "custom-call()", 4_000_000)]
        for name, ns in events:
            ops.append(ev(name, at, ns))
            at += ns
    return [NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                            NS(name="XLA Modules", events=mods)])]


def view(cfg_name="laguna-s-2.1-l5", banded=True):
    cfg = config(cfg_name)
    return NS(trace=tr.reduce_planes(planes(banded), 1), window_s=1.0,
              counters={"steps": 2, "rows_per_step": 1, "window_s": 1.0},
              cell={"name": "x", "chips": 1}, cfg=cfg, traffic={},
              peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
              flops=harness.module("flops", cfg["flops"]))


def test_the_windowed_kernels_are_told_from_the_whole_triangles():
    run = view()
    # 3 x (2 + 4) of 100 ms; the global pair is 8 more in `flash_share_of_step.train`
    assert read("window_flash_share_of_step.train", run) == pytest.approx(18.0)
    assert read("flash_share_of_step.train", run) == pytest.approx(26.0)


def test_roofline_by_hand():
    run = view()
    f = run.flops
    least = max(f.window_flash_flops(run.cfg, 1) / 197e12, f.window_flash_bytes(run.cfg, 1) / 819e9)
    assert least == f.window_flash_flops(run.cfg, 1) / 197e12      # the operations bound it
    assert read("window_flash_roofline.train", run) == pytest.approx(100 * least * 2 / 36e-3)
    assert 18.9 < read("window_flash_roofline.train", run) < 19.1   # 3.42 ms of 18 a step
    # all the flash kernels over triangles + bands: the accepted reader on the same trace
    both = max(f.flash_flops(run.cfg, 1) / 197e12, f.flash_bytes(run.cfg, 1) / 819e9)
    assert read("flash_roofline.train", run) == pytest.approx(100 * both * 2 / 52e-3)
    assert read("flash_roofline.train", run) < 100


def test_nothing_to_read_is_none_not_an_error(monkeypatch, tmp_path):
    for name in NAMES[:2]:
        assert read(name, view(banded=False)) is None, name                  # no window in a name
    # another model's counts have no band: the roofline is left out, the share is read
    other = view("ouro-2.6b-l6")
    assert read(NAMES[0], other) is None and read(NAMES[1], other) == pytest.approx(18.0)
    monkeypatch.setattr(sr, "scope_account", lambda run: None)
    assert read(NAMES[2], NS(cell={"name": "cell", "chips": 1}, trace=None)) is None
    monkeypatch.undo()
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))                 # no capture
    monkeypatch.setattr(sr, "_cache", {})
    assert read(NAMES[2], NS(cell={"name": "cell", "chips": 1}, trace=None)) is None
    run = NS(counters={"steps": 3, "window_s": 1.0})
    for entry in (None, {"steps": 3}, {"steps": 3, "attention": []}):        # no log, no counter
        monkeypatch.setattr(span_reduce, "fit_entry", lambda run, e=entry: e)
        assert read(NAMES[3], run) is None


def test_attention_share_takes_every_part_of_the_kind(monkeypatch):
    """One run of 1000 ns: an attention block's projection (100), rope kernel
    (20), gates (30), banded kernel (50), output product (60), the same behind
    (300) and recomputed (40); the experts (200) are not the attention's."""
    blk = "dl4j.L3.sublayerblock"
    fwd = f"jit(step)/jvp({blk})/dl4j.gatedattention/"
    bwd = f"jit(step)/transpose(jvp({blk}))/dl4j.gatedattention/"
    again = f"jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/{blk}/dl4j.gatedattention/"
    md = {"%proj.1": meta(fwd + "proj/dot_general:"), "%rope.2": meta(fwd + "rope/pallas_call:"),
          "%gate.3": meta(fwd + "gates/mul:"), "%flash.4": meta(fwd + "attend/pallas_call:"),
          "%out.5": meta(fwd + "out/dot_general:"), "%back.6": meta(bwd + "attend/pallas_call:"),
          "%re.7": meta(again + "proj/dot_general:"),
          "%moe.8": meta("jit(step)/jvp(dl4j.L4.sublayerblock)/dl4j.routedexperts/product/x:"),
          "%copy.9": meta("jit(step)/copy:", "data formatting")}
    ops, at = [], 0
    for name, ns in (("%proj.1", 100), ("%rope.2", 20), ("%gate.3", 30), ("%flash.4", 50),
                     ("%out.5", 60), ("%back.6", 300), ("%re.7", 40), ("%moe.8", 200),
                     ("%copy.9", 200)):
        ops.append((at, at + ns, name))
        at += ns
    acct = sr.account(ops, "jit_step(7)", [(0, 1000)], md, PARTS)
    monkeypatch.setattr(sr, "scope_account", lambda run: acct)
    run = NS(cell={"name": "cell", "chips": 1}, trace=None)
    assert read("attention_share_of_step.train", run) == pytest.approx(60.0)
    assert read("attention_rope_share_of_step.train", run) == pytest.approx(2.0)


def test_band_fill_is_read_from_the_windows_fit(monkeypatch):
    layer = {"steps": 21, "window": 512, "n_heads": 36, "n_kv_heads": 4,
             "band_keys_per_query": 496.03, "visited_keys_per_query": 992.0, "band_fill": 0.50003}
    fit = {"steps": 21, "attention": [dict(layer, layer="layer_3"),
                                      dict(layer, layer="layer_5", band_fill=0.8)]}
    monkeypatch.setattr(span_reduce, "fit_entry", lambda run: fit)
    assert read("window_band_fill.train", NS()) == pytest.approx(50.003)    # the least over the layers


def test_benchmark_json_lists_them_for_the_one_cell():
    import json
    import os

    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert set(NAMES) <= set(by_name)                                         # listed
    for name in NAMES:
        m = by_name[name]
        assert m["workloads"] == ["laguna_train_t8192_b1"] and m["moves"] == "train_throughput"
        assert os.path.exists(os.path.join(harness.HERE, "metrics", name + ".py"))
    assert [(by_name[n]["layer"], by_name[n]["source"], by_name[n]["better"]) for n in NAMES] == [
        ("kernels", "device_trace", "higher"), ("kernels", "device_trace", "lower"),
        ("kernels", "device_trace", "lower"), ("attention", "program_counter", "higher")]
    cell = harness.load_cell("laguna_train_t8192_b1")
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "laguna-s-2.1-l5", "train_ids_t8192_b1")
    assert {m["name"] for m in cell["end_to_end"]} == {"train_throughput", "setup_s"}
    listed = {m["name"] for m in cell["per_layer"]}
    assert set(NAMES) <= listed
    assert {"flash_roofline.train", "flash_share_of_step.train", "mfu.train",
            "step_scoped_share.train", "head_loss_share_of_step.train",
            "forward_share_of_step.train", "attention_rope_share_of_step.train",
            "expert_share_of_step.train", "expert_product_share_of_step.train",
            "expert_load_max_over_mean.train", "expert_dropped_assignments.train",
            "expert_capacity_fill.train", "compiles_in_window.train",
            "device_idle_share.train"} <= listed
    # and no reader of a recurrent mixer, a latent head, a loop or an exit gate
    assert not {n for n in listed if n.startswith(("delta_", "ssd_", "kda_", "mixer_", "latent_",
                                                   "rope_share", "shortconv_", "loop_", "norm_",
                                                   "exit_"))}
    # the cell joined lists and changed nothing else: every other cell's metrics are the seed's
    assert len(bench["workloads"]) == 9 and len(bench["configs"]) == 8
    assert [w["name"] for w in bench["workloads"] if w["chips"] != 1] == []
