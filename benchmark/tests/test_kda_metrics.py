"""The two readers of the KDA core on a synthetic trace: each reads a number
where the loops over row groups (or the scan's carried state) are in the
trace, and None — the metric is left out of the line — for a program or a
configuration without them (the parent commit, another model)."""
from types import SimpleNamespace as NS

import pytest

from benchmark import harness, trace_reduce as tr
from benchmark.tests.test_hybrid_metrics import config, read
from benchmark.tests.test_trace_reduce import ev


def planes(core=True):
    """Two runs of the step, 1000 ns each. With the core: the forward loop
    over 2 row groups carrying [q | k | v] as bf16 [2, 128, 1, 96, 64, 128]
    (100 ns), the backward one carrying a head-channel array re-tiled
    (200 ns) with the scan's own loop nested in it, a scan outside any row
    loop (50 ns), and two loops that are not the core's: the loss over 10
    row blocks, the state-space core's state."""
    ops, mods = [], []
    for base in (0, 5000):
        mods.append(ev("jit_step(7)", base, 1000))
        ops.append(ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", base, 1000))
        if not core:
            continue
        ops += [
            ev("%while.3 = (s32[], bf16[2,128,1,96,64,128]{5,4,3,2,1,0}, bf16[2,1,8192,4096]{3,2,1,0}) while(%t.1), condition=%c, body=%b",
               base + 0, 100),
            ev("%while.9 = (s32[], f32[2,128,1,32,64,128]{5,4,3,2,1,0}) while(%t.2), condition=%c2, body=%b2",
               base + 200, 200),
            ev("%while.10 = (s32[], f32[1,32,128,128]{3,2,1,0}, f32[128,1,32,128,128]{4,3,2,1,0}) while(%t.3), condition=%c3, body=%b3",
               base + 250, 100),
            ev("%while.12 = (s32[], bf16[2,32,128,128]{3,2,1,0}) while(%t.4), condition=%c4, body=%b4",
               base + 500, 50),
            ev("%while.20 = (s32[], bf16[10,2048,2304]{2,1,0}) while(%t.5), condition=%c5, body=%b5",
               base + 600, 70),
            ev("%while.21 = (s32[], f32[1,64,64,128]{3,2,1,0}) while(%t.6), condition=%c6, body=%b6",
               base + 700, 70),
        ]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                          NS(name="XLA Modules", events=mods)])
    return [dev]


def view(cfg_name="kimi-linear-48b-a3b-l5", core=True, rows=2):
    cfg = config(cfg_name)
    return NS(trace=tr.reduce_planes(planes(core), 1), window_s=1.0,
              counters={"steps": 2, "rows_per_step": rows, "window_s": 1.0},
              cell={"name": "x", "chips": 1}, cfg=cfg, traffic={},
              peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
              flops=harness.module("flops", cfg["flops"]))


def test_core_share_counts_row_loops_and_the_scan_once():
    # 100 + 200 (the nested scan inside it) + 50 of 1000 ns a run
    assert read("kda_share_of_step.train", view()) == pytest.approx(35.0)


def test_roofline_by_hand():
    run = view()
    f = run.flops
    least = max(f.kda_flops(run.cfg, 2) / 197e12, f.kda_bytes(run.cfg, 2) / 819e9)
    assert least == f.kda_bytes(run.cfg, 2) / 819e9            # the bytes bound it
    assert read("kda_roofline.train", run) == pytest.approx(100 * least * 2 / 700e-9)


def test_nothing_to_read_is_none_not_an_error():
    for name in ("kda_share_of_step.train", "kda_roofline.train"):
        assert read(name, view(core=False)) is None, name                   # the parent's trace
        assert read(name, view("qwen3-next-80b-a3b-l4")) is None, name      # another model
        assert read(name, view("nemotron-3-nano-30b-a3b-l9")) is None, name
        assert read(name, view("gpt2-small", rows=8)) is None, name


def test_the_other_cores_readers_find_nothing_here():
    for name in ("ssd_share_of_step.train", "delta_core_share_of_step.train"):
        assert read(name, view()) is None, name


def test_the_new_cell_lists_its_metrics():
    cell = harness.load_cell("kimilinear_train_t8192")
    names = {m["name"] for m in cell["per_layer"]}
    assert {"kda_share_of_step.train", "kda_roofline.train", "mfu.train", "flash_roofline.train",
            "flash_share_of_step.train", "expert_share_of_step.train",
            "expert_load_max_over_mean.train", "expert_dropped_assignments.train",
            "expert_capacity_fill.train", "device_step_ms.train"} <= names
    assert len(names) == 22
    assert not {n for n in names if n.startswith(("delta_", "ssd_"))}
    assert cell["traffic_params"]["kind"] == "train_stream_ids" and cell["chips"] == 1
    assert {m["name"] for m in cell["end_to_end"]} == {"train_throughput", "setup_s"}
    for other in ("qwen3next_train_t8192", "nemotron3nano_train_t8192", "gpt2s_train_t1024"):
        assert not {m["name"] for m in harness.load_cell(other)["per_layer"]
                    if m["name"].startswith("kda_")}


def test_expert_reader_sizes_the_buffer_from_the_new_configuration():
    experts = harness.module("metrics", "expert_share_of_step.train")
    assert experts.sizes(view()) == (16384, 256, 131072, 131072)    # every assignment a row
