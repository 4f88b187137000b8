"""The readers this PR adds, on a synthetic trace and fit log: each reads a
number where there is something to read and None (the metric is left out of
the line) where there is not — a parent program without the spans, counters
or kernels, or a configuration without the layer."""
import json
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import harness, trace_reduce as tr
from benchmark.tests.test_trace_reduce import ev

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STATE = "f32[1,32,128,128]{3,2,1,0}"


def config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def planes(with_layers=True):
    """Two runs of the step, 1000 ns each. With the layers: a flash kernel
    100 ns; two delta scans (forward 100 ns, backward 200 ns) and a while
    that carries no state; the experts' grouped products 2 x 30 ns, their
    top-k sort 20 ns and scatter 20 ns, a sort of another size and the
    shared expert's activation."""
    ops, mods = [], []
    for base in (0, 5000):
        mods.append(ev("jit_step(7)", base, 1000))
        ops.append(ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", base, 1000))
        if not with_layers:
            continue
        ops += [
            ev("%jvp_dl4j_flash_fwd_bh32_t8192_d256_bq256_bk512_bfloat16_.1 = bf16[32,8192,256]{2,1,0} custom-call(...)",
               base + 0, 100),
            ev(f"%while.4 = (s32[], {STATE}, f32[128,1,32,128,128]{{4,3,2,1,0}}) while(%tuple.1), condition=%c, body=%b",
               base + 100, 100),
            ev("%while.9 = (s32[], bf16[1,32,128,128]{3,2,1,0}, bf16[128,1,32,128,128]{4,3,2,1,0}) while(%tuple.2), condition=%c2, body=%b2",
               base + 200, 200),
            ev("%while.11 = (s32[], f32[2048,18992]{1,0}) while(%tuple.3), condition=%c3, body=%b3",
               base + 400, 50),
            ev("%ragged-dot-none.1 = bf16[163840,1024]{1,0} custom-call(...)", base + 500, 30),
            ev("%ragged-dot-metadata.1 = (s32[33]{0}) custom-call(...)", base + 530, 30),
            ev("%sort = (f32[16384,512]{0,1}, s32[16384,512]{0,1}) sort(f32[16384,512]{0,1} %p, s32[16384,512]{0,1} %i), dimensions={1}",
               base + 600, 20),
            ev("%fusion.3 = f32[16384,2048]{1,0} fusion(f32[16384,2048]{1,0} %z, s32[163840]{0} %i, f32[163840,2048]{1,0} %u), kind=kInput",
               base + 620, 20),
            ev("%sort.5 = (f32[77]{0}) sort(f32[77]{0} %x)", base + 700, 40),
            ev("%fusion.9 = bf16[16384,512]{1,0} fusion(bf16[16384,1024]{1,0} %h), kind=kLoop",
               base + 800, 40),          # the shared expert: not the routed layer's
        ]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                          NS(name="XLA Modules", events=mods)])
    return [dev]


def view(cfg_name="qwen3-next-80b-a3b-l4", with_layers=True, rows=2):
    cfg = config(cfg_name)
    return NS(trace=tr.reduce_planes(planes(with_layers), 1), window_s=1.0,
              counters={"steps": 2, "rows_per_step": rows, "window_s": 1.0},
              cell={"name": "x", "chips": 1}, cfg=cfg, traffic={},
              peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
              flops=harness.module("flops", cfg["flops"]))


def read(name, run):
    return harness.module("metrics", name).read(run)


def test_shares_of_the_step():
    run = view()
    assert read("flash_share_of_step.train", run) == pytest.approx(10.0)


def test_nothing_to_read_is_none_not_an_error():
    """A kernel found by its name: the scope readers' cases are
    test_scoped_readers.py's."""
    assert read("flash_share_of_step.train", view(with_layers=False)) is None
    gpt2 = view("gpt2-small", rows=8)          # the layers' events, another model
    assert read("flash_share_of_step.train", gpt2) == pytest.approx(10.0)


def test_expert_counters_come_from_the_windows_fit(monkeypatch):
    from deeplearning4j_tpu import telemetry

    experts = [
        {"layer": "layer_1", "steps": 2, "assignments_per_step": 10000.0,
         "load_max_over_mean": 1.4, "dropped_assignments": 0, "capacity_fill": 0.78},
        {"layer": "layer_2", "steps": 2, "assignments_per_step": 11000.0,
         "load_max_over_mean": 2.5, "dropped_assignments": 3, "capacity_fill": 0.86}]
    log = [{"steps": 3, "wall_s": 0.99, "phases": {}},
           {"steps": 2, "wall_s": 0.98, "phases": {}, "experts": experts}]
    monkeypatch.setattr(telemetry, "fit_log", lambda: log)
    run = view()
    assert read("expert_load_max_over_mean.train", run) == 2.5
    assert read("expert_dropped_assignments.train", run) == 3
    assert read("expert_capacity_fill.train", run) == pytest.approx(86.0)
    log[1].pop("experts")                       # a program that counts nothing
    for name in ("expert_load_max_over_mean.train", "expert_dropped_assignments.train",
                 "expert_capacity_fill.train"):
        assert read(name, run) is None


def test_every_new_metric_has_a_reader_and_lists_its_cells():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(HERE, "metrics", m["name"] + ".py")), m["name"]
        assert set(m["workloads"]) <= cells
    for name in ("qwen3next_train_t8192", "gpt2s_train_t1024_ids"):
        cell = harness.load_cell(name)
        assert cell["traffic_params"]["kind"] == "train_stream_ids"
        assert {m["name"] for m in cell["end_to_end"]} == {"train_throughput", "setup_s"}
        assert len(cell["per_layer"]) >= 16
