"""`delta_core_share_of_step.train` by hand on a synthetic trace: two loops
over the row groups (forward, and backward with a scan over chunks nested in
it), a loop that carries the groups re-tiled, and loops that are not the
layer's (the row-blocked loss, a scan outside any group loop)."""
import json
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import harness, trace_reduce as tr
from benchmark.tests.test_hybrid_metrics import config, read
from benchmark.tests.test_trace_reduce import ev

STATE = "f32[1,32,128,128]{3,2,1,0}"
FWD = ("%while.426 = (s32[], bf16[2,1,8192,8192]{3,2,1,0}, bf16[2,1,8192,4096]{3,2,1,0}) "
       "while(%tuple.1), condition=%c, body=%b")
BWD = ("%while.453 = (s32[], bf16[2,1,8192,8192]{3,2,1,0}, f32[2,1,8192,64]{3,2,1,0}) "
       "while(%tuple.2), condition=%c2, body=%b2")
RETILED = ("%while.77 = (s32[], bf16[2,128,1,32,64,128]{5,4,3,1,2,0}, bf16[2,1,8192,4096]{3,2,1,0}) "
           "while(%tuple.5), condition=%c5, body=%b5")
SCAN = f"%while.9 = (s32[], {STATE}, bf16[128,1,32,128,128]{{4,3,2,1,0}}) while(%tuple.3), condition=%c3, body=%b3"
LOSS = "%while.11 = (s32[], f32[2048,18992]{1,0}, bf16[8,2048,2048]{2,1,0}) while(%tuple.4), condition=%c4, body=%b4"


def view(loops, cfg_name="qwen3-next-80b-a3b-l4", rows=2):
    """Two runs of the step, 1000 ns each, with the given (instruction,
    offset, duration) loops in each."""
    ops, mods = [], []
    for base in (0, 5000):
        mods.append(ev("jit_step(7)", base, 1000))
        ops.append(ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", base, 1000))
        ops += [ev(name, base + at, ns) for name, at, ns in loops]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                          NS(name="XLA Modules", events=mods)])
    return NS(trace=tr.reduce_planes([dev], 1), window_s=1.0,
              counters={"steps": 2, "rows_per_step": rows, "window_s": 1.0},
              cell={"name": "x", "chips": 1}, cfg=config(cfg_name), traffic={},
              peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
              flops=None)


LOOPS = [(FWD, 0, 100), (BWD, 200, 300), (SCAN, 250, 50),      # nested in the backward
         (SCAN, 600, 70), (LOSS, 700, 100)]                    # not the layer's


def test_the_core_is_the_loops_over_row_groups_nested_scans_included():
    run = view(LOOPS)
    assert read("delta_core_share_of_step.train", run) == pytest.approx(40.0)
    # the scans are found wherever they run: the inner part, and one outside
    assert read("delta_scan_share_of_step.train", run) == pytest.approx(12.0)


def test_a_loop_that_carries_the_groups_re_tiled_is_found_by_its_size():
    run = view([(RETILED, 100, 250)])
    assert read("delta_core_share_of_step.train", run) == pytest.approx(25.0)


@pytest.mark.parametrize("loops,cfg,rows", [
    ([(SCAN, 0, 100), (LOSS, 200, 100)], "qwen3-next-80b-a3b-l4", 2),  # core not mapped
    ([], "qwen3-next-80b-a3b-l4", 2),                                  # a bare trace
    (LOOPS, "gpt2-small", 8),                                          # another model
    (LOOPS, "qwen3-next-80b-a3b-l4", 1),                               # one row: no groups
])
def test_nothing_to_read_is_none_not_an_error(loops, cfg, rows):
    assert read("delta_core_share_of_step.train", view(loops, cfg, rows)) is None


def test_the_metric_is_declared_for_the_hybrid_cell_alone():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (m,) = [m for m in bench["per_layer"] if m["name"] == "delta_core_share_of_step.train"]
    assert m == {"name": "delta_core_share_of_step.train", "unit": "%", "better": "lower",
                 "source": "device_trace", "layer": "kernels", "moves": "train_throughput",
                 "workloads": ["qwen3next_train_t8192"]}
    assert bench["per_layer"][-1] is m
