"""The three readers of the experts' exchange and the collective reader PR 31
kept: the two trace readers on a scope account built by hand (an exchange out
and back, forward, recomputed and transposed), the fill on the window's
counters; each is None — the metric is left out of the line — where there is
nothing to read (the parent's program, one rank, no capture)."""
import json
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import harness, scope_reduce as sr, trace_reduce as tr
from benchmark.tests.test_hybrid_metrics import read
from benchmark.tests.test_scope_reduce import meta
from benchmark.tests.test_trace_reduce import ev
from benchmark.traffic import train_stream_ids_mesh as mesh_kind
from deeplearning4j_tpu.telemetry import trace as trace_mod

PARTS = frozenset(trace_mod.SCOPE_PARTS)
NAMES = ("expert_exchange_share_of_step.train", "expert_exchange_gbps.train",
         "expert_pair_fill_max.train", "allreduce_exposed_ms.train")
CELL = "mellum2_train_t8192_ep4"


def account():
    """One run of 1000 ns: out 40 and back 30 forward, both again in the
    recompute (35 + 25), their transposes 45 + 50; the bucket (20), a grouped
    product (200) and an attention kernel (100) are not the exchange's."""
    blk = "dl4j.L2.sublayerblock"
    fwd = f"jit(step)/jvp({blk})/dl4j.routedexperts/shard_map/"
    bwd = f"jit(step)/transpose(jvp(jvp()))/checkpoint/{blk}/dl4j.routedexperts/shard_map/"
    again = (f"jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/{blk}/"
             "dl4j.routedexperts/shard_map/")
    md = {"%all_to_all.1": meta(fwd + "exchange/out/all_to_all:"),
          "%all_to_all.2": meta(fwd + "exchange/back/all_to_all:"),
          "%all_to_all.3": meta(again + "exchange/out/all_to_all:"),
          "%all_to_all.4": meta(again + "exchange/back/all_to_all:"),
          "%all_to_all.5": meta(bwd + "exchange/back/all_to_all:"),
          "%all_to_all.6": meta(bwd + "exchange/out/all_to_all:"),
          "%gather.7": meta(fwd + "bucket/gather:"),
          "%dot.8": meta(fwd + "product/ragged_dot:"),
          "%flash.9": meta("jit(step)/jvp(dl4j.L1.sublayerblock)/dl4j.gatedattention/attend/x:")}
    ops, at = [], 0
    for name, ns in (("%all_to_all.1", 40), ("%all_to_all.2", 30), ("%all_to_all.3", 35),
                     ("%all_to_all.4", 25), ("%all_to_all.5", 45), ("%all_to_all.6", 50),
                     ("%gather.7", 20), ("%dot.8", 200), ("%flash.9", 100)):
        ops.append((at, at + ns, name))
        at += ns
    return sr.account(ops, "jit_step(7)", [(0, 1000)], md, PARTS)


def test_the_exchange_is_read_by_its_scopes(monkeypatch):
    acct = account()
    assert ("2", "routedexperts", ("exchange", "out")) in acct.rows
    assert ("2", "routedexperts", ("exchange", "back")) in acct.rows
    monkeypatch.setattr(sr, "scope_account", lambda run: acct)
    run = NS(cell={"name": CELL, "chips": 4}, trace=None,
             counters={"exchange_bytes_per_step": 1650})
    assert read(NAMES[0], run) == pytest.approx(22.5)          # 225 of 1000 ns, recompute too
    # the counter's bytes are the forward's and the backward's: 165 ns of them
    assert read(NAMES[1], run) == pytest.approx(1650 / 165e-9 / 1e9)
    assert read("expert_share_of_step.train", run) == pytest.approx(44.5)
    assert read("recompute_share_of_step.train", run) == pytest.approx(6.0)


def test_the_fill_is_the_windows_counter():
    experts = [{"layer": "layer_2", "pair_fill_max": 0.70, "rank_load_max_over_mean": 1.02,
                "exchange_bytes": 10}, {"layer": "layer_4", "pair_fill_max": 0.74,
                                        "rank_load_max_over_mean": 1.01, "exchange_bytes": 12}]
    seen = mesh_kind.exchange_counters(experts + [dict(experts[0], pair_fill_max=0.71)])
    assert seen == {"pair_fill_max": 0.74, "rank_load_max_over_mean": 1.02,
                    "exchange_bytes_per_step": 22}
    assert read(NAMES[2], NS(counters=seen)) == pytest.approx(74.0)
    assert mesh_kind.exchange_counters([{"layer": "layer_2", "dropped_assignments": 0}]) == {}


def test_nothing_to_read_is_none_not_an_error(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))                 # no capture
    monkeypatch.setattr(sr, "_cache", {})
    bare = NS(cell={"name": "cell", "chips": 1}, trace=None, counters={})
    for name in NAMES[:3]:
        assert read(name, bare) is None, name
    acct = sr.account([(0, 100, "%dot.8")], "jit_step(7)", [(0, 1000)], {
        "%dot.8": meta("jit(step)/jvp(dl4j.L2.x)/dl4j.routedexperts/product/ragged_dot:")}, PARTS)
    monkeypatch.setattr(sr, "scope_account", lambda run: acct)              # one rank: no exchange
    run = NS(cell={"name": "cell", "chips": 1}, trace=None,
             counters={"exchange_bytes_per_step": 0})
    assert read(NAMES[0], run) is None and read(NAMES[1], run) is None


def test_exposed_collective_time_is_read_on_four_chips():
    """Two runs of the step: an all-to-all alone for 3 ms, an all-reduce under
    a fusion for 2 ms and alone for 1 ms."""
    ops, mods = [], []
    for base in (0, 500_000_000):
        mods.append(ev("jit_step(7)", base, 100_000_000))
        ops += [ev("%all-to-all.1 = bf16[4,8,16] all-to-all(bf16[4,8,16] %p)", base, 3_000_000),
                ev("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", base + 5_000_000,
                   2_000_000),
                ev("%all-reduce.3 = f32[8] all-reduce(f32[8] %q)", base + 5_000_000, 3_000_000)]
    planes = [NS(name=f"/device:TPU:{i}", lines=[NS(name="XLA Ops", events=ops),
                                                 NS(name="XLA Modules", events=mods)])
              for i in range(4)]
    run = NS(trace=tr.reduce_planes(planes, 4), cell={"name": CELL, "chips": 4})
    assert read(NAMES[3], run) == pytest.approx(4.0)
    assert read(NAMES[3], NS(trace=run.trace, cell={"name": "x", "chips": 1})) is None


def test_benchmark_json_lists_them_for_the_one_cell():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "train_throughput"
        assert os.path.exists(os.path.join(harness.HERE, "metrics", name + ".py"))
    assert [(by_name[n]["layer"], by_name[n]["source"], by_name[n]["better"], by_name[n]["unit"])
            for n in NAMES] == [
        ("routed experts", "device_trace", "lower", "%"),
        ("routed experts", "device_trace", "higher", "GB/s"),
        ("routed experts", "program_counter", "lower", "%"),
        ("device", "device_trace", "lower", "ms")]
    cell = harness.load_cell(CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        4, "mellum2-12b-a2.5b-l4", "train_ids_mesh_t8192_b1")
    assert cell["traffic_params"]["kind"] == "train_stream_ids_mesh"
    assert {m["name"] for m in cell["end_to_end"]} == {"train_throughput", "setup_s"}
    listed = {m["name"] for m in cell["per_layer"]}
    assert set(NAMES) <= listed
    assert {"flash_roofline.train", "window_flash_roofline.train", "window_band_fill.train",
            "mfu.train", "attention_share_of_step.train", "attention_rope_share_of_step.train",
            "expert_share_of_step.train", "expert_product_share_of_step.train",
            "expert_load_max_over_mean.train", "expert_dropped_assignments.train",
            "expert_capacity_fill.train", "recompute_share_of_step.train",
            "compiles_in_window.train", "device_idle_share.train"} <= listed
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
