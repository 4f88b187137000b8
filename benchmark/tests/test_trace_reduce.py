"""The reduction from a profiler trace to numbers: on planes built by hand
(busy union, gaps, per-name sums, collective overlap, idle attribution) and
on a small trace recorded on a TPU v5e and kept beside this file
(benchmark/tests/record_small_trace.py made it)."""
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import trace_reduce as tr

SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "small_tpu.xplane.pb")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes():
    """Two steps on chip 0. Step: a flash kernel 100..300, a fusion nested
    in a while 300..700 (while 300..800), an all-reduce 750..950 that
    overlaps the while until 800 and is exposed 800..950."""
    ops, mods = [], []
    for base in (0, 2000):
        mods.append(ev("jit_step(123)", base + 100, 850))
        ops += [
            ev("%jvp_dl4j_flash_fwd_bh96_t1024_d64_bq256_bk512_bfloat16_.12 = bf16[8,12,1024,64]{3,2,1,0} custom-call(...)",
               base + 100, 200),
            ev("%while.3 = (f32[8]{0}) while(...)", base + 300, 500),
            ev("%fusion.77 = f32[8,1024]{1,0:T(8,128)} fusion(f32[8]{0} %p), kind=kLoop",
               base + 300, 400),
            ev("%all-reduce.1 = f32[1000]{0} all-reduce(f32[1000]{0} %g)", base + 750, 200),
        ]
    mods.append(ev("jit_other(9)", 1500, 10))
    ops.append(ev("%copy.1 = f32[2]{0} copy(f32[2]{0} %x)", 1500, 10))
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                          NS(name="XLA Modules", events=mods),
                                          NS(name="Steps", events=[ev("0", 0, 5000)])])
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev("bench.next_batch", 1000, 400), ev("XlaLinearize", 1000, 1100),
        ev("SomethingElse", 0, 5000)])])
    return [dev, host, NS(name="/host:metadata", lines=[])]


def test_busy_union_gaps_and_sums():
    red = tr.reduce_planes(planes(), chips=1)
    # per step 100..950 = 850, plus the 10 ns copy
    assert red.busy_s == pytest.approx((850 * 2 + 10) / 1e9)
    assert (red.t_min, red.t_max) == (100, 2950)
    assert red.idle_gaps() == [(950, 1500), (1510, 2100)]
    name, runs = red.main_module()
    assert name == "jit_step" and len(runs) == 2
    assert red.module_gaps_ms() == [pytest.approx((2100 - 950) / 1e6)]
    fam = red.family_seconds()
    assert fam["dl4j_flash_fwd"] == pytest.approx(400 / 1e9)
    assert fam["fusion"] == pytest.approx(800 / 1e9)
    assert fam["while"] == pytest.approx(1000 / 1e9)
    assert red.kernel_seconds("dl4j_flash") == pytest.approx(400 / 1e9)
    assert red.kernel_seconds("dl4j_xent") == 0


def test_collective_time_not_hidden_behind_compute():
    red = tr.reduce_planes(planes(), chips=1)
    assert red.collective_exposed_s() == pytest.approx(2 * 150 / 1e9)


def test_idle_time_by_host_span_and_breakdown():
    red = tr.reduce_planes(planes(), chips=1)
    idle = red.idle_by_host_span()
    assert idle["bench.next_batch"] == pytest.approx(400 / 1e9)     # 1000..1400
    assert idle["XlaLinearize"] == pytest.approx((500 + 590) / 1e9)
    assert idle["no host span"] == pytest.approx(50 / 1e9)           # 950..1000
    assert "SomethingElse" not in idle       # not a span the benchmark reads
    b = red.breakdown()
    assert b["device_ops"][0] == ["while.3 -> f32[8]", pytest.approx(1000 / 1e9)]
    assert ["dl4j_flash_fwd", pytest.approx(400 / 1e9)] in b["device_ops"]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_names():
    n = "%transpose_jvp_dl4j_flash_bwd_dkv_bh96_t1024_d64_bq256_bk512_bfloat16__.12 = (bf16[1]) custom-call()"
    assert tr.family(n) == "dl4j_flash_bwd_dkv" == tr.describe(n)
    assert tr.family("%divide_subtract_fusion.1 = f32[2]{0} fusion()") == "divide_subtract_fusion"
    f = "%fusion.1380 = (bf16[50257]{0:T(1024)}, bf16[8,1024,50257]{1,2,0}) fusion(bf16[8,1024]{1,0} %a), kind=kLoop"
    assert tr.describe(f) == "fusion.1380 -> bf16[8,1024,50257]"


def test_a_trace_without_the_cells_chips_is_an_error():
    with pytest.raises(RuntimeError, match="chips"):
        tr.reduce_planes(planes(), chips=4)


def test_small_recorded_tpu_trace():
    red = tr.reduce_file(SMALL, chips=1)
    name, runs = red.main_module()
    assert name == "jit_small_step" and len(runs) == 3
    busy = red.busy_s
    assert 0 < busy < red.window_s
    # three pauses of 10 ms between the runs
    assert all(g > 9.0 for g in red.module_gaps_ms())
    assert red.idle_by_host_span()["bench.pause"] >= 0.019
    assert sum(red.family_seconds().values()) >= busy * 0.99
    assert red.breakdown()["device_ops"]
