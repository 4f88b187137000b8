"""What PR 51 changed in the harness, on the CPU: the set-up clock runs from
the instant the runtime has the chip, less the reference's seconds — its
seeded weights too — and reports the launcher's seconds apart; a program
that cannot build the configuration stops before it waits for the chip; both
train drivers print the window's steps and report their median; the
breakdown lists every `dl4j_*` kernel family and can name `dl4j.*` spans."""
from types import SimpleNamespace as NS

import pytest

from benchmark import harness, trace_reduce as tr
from benchmark.tests import tiny, tiny_ids
from benchmark.tests.test_correct import TRAIN, run_main, run_mod, tiny_cell
from benchmark.tests.test_trace_reduce import ev
from benchmark.tests.test_train_stream_ids import cell as ids_cell
from benchmark.traffic import train_stream as ts


def clocked(monkeypatch, *instants):
    ticks = iter(instants)
    monkeypatch.setattr(harness.time, "perf_counter", lambda: next(ticks))


def test_setup_runs_from_the_chip_less_the_reference_plus_the_early_program_seconds(monkeypatch):
    """Process start 100. The configuration is built 103..105 (the program's:
    set-up), the chip is there at 116, the seeded weights take 120..124 and
    the reference's steps 124..150, the window opens at 170."""
    clocked(monkeypatch, 103, 105, 116, 120, 124, 124, 150)
    setup = harness.Setup(100.0)
    with setup.early():
        pass
    setup.chip_ready()
    with setup.reference("weights"):
        pass
    with setup.reference():
        pass
    assert setup.launch_s == pytest.approx(14.0)                 # 16 s to the chip less the 2
    assert setup.reference_s == pytest.approx(30.0)
    assert setup.reference_parts == {"weights": pytest.approx(4.0), "steps": pytest.approx(26.0)}
    assert setup.setup_s(170.0) == pytest.approx(170 - 116 + 2 - 30)
    line = setup.report(170.0)
    # the clock before PR 51: process start to the window less the steps alone
    assert "setup_s 26.00" in line and "the clock before PR 51: 44.00" in line
    assert "launch_s 14.00" in line and "weights 4.00" in line


@pytest.mark.parametrize("cell", ["dense_labels", "integer_labels"])
def test_a_run_reports_launch_apart_and_its_windows_steps(cell, monkeypatch, capsys):
    cfg_cell = (tiny_cell(tiny.gpt2(), TRAIN) if cell == "dense_labels"
                else ids_cell(tiny_ids.qwen3_next()))
    seen = {}
    real = harness.module

    def module(kind, name):
        mod = real(kind, name)
        if kind == "traffic":
            run = mod.run

            def wrapped(ctx):
                out = run(ctx)
                seen.update(out["counters"])
                return out
            mod.run = wrapped
        return mod

    monkeypatch.setattr(harness, "module", module)
    result, out = run_main(monkeypatch, capsys, cfg_cell)
    assert result["correct"] is True, out
    assert result["device"]["launch_s"] >= 0 and "memory_peak_bytes" in result["device"]
    assert set(result["metrics"]) == {"train_throughput", "setup_s"}     # launch_s is no metric
    assert 0 < result["metrics"]["setup_s"]["value"]
    lines = out.splitlines()
    steps = next(l for l in lines if l.startswith("[bench] window steps"))
    assert "median_s" in steps and "longest_s" in steps
    clock = next(l for l in lines if l.startswith("[bench] launch_s"))
    assert "not in setup_s: weights" in clock and "the clock before PR 51" in clock
    # the seeded weights are the reference's: their mark says so
    assert any("seeded weights" in l and "not in setup_s" in l for l in lines)
    # the median the per-layer reader reports is the printed one
    median = harness.module("metrics", "step_wall_median_ms.train").read(NS(counters=seen))
    assert median == pytest.approx(1e3 * float(steps.split("'median_s': ")[1].split(",")[0]))
    assert seen["steps"] == result["attempted"] and seen["compiles_in_window"] == 0


def test_the_median_step_is_untouched_by_one_stall():
    """20 steps of 50 ms, one of them 2 s late: the rate falls by two
    thirds, the median does not move."""
    import numpy as np

    times = np.cumsum([0.05] * 20)
    stalled = times.copy()
    stalled[7:] += 2.0
    for t in (times, stalled):
        rep = ts.step_report(t, 0.0)
        assert rep["median_s"] == pytest.approx(0.05) and rep["steps"] == 20
        assert ts.window_counters(20, 8, t[-1], 0, rep)["step_wall_median_ms"] == pytest.approx(50.0)
    assert ts.step_report(stalled, 0.0)["longest_s"][8] == pytest.approx(2.05)
    empty = ts.step_report([], 0.0)
    assert empty["median_s"] is None
    assert "step_wall_median_ms" not in ts.window_counters(0, 8, 1.0, 0, empty)
    assert harness.module("metrics", "step_wall_median_ms.train").read(NS(counters={})) is None


def test_a_program_that_cannot_build_the_model_stops_before_the_chip(monkeypatch):
    def no_chip(chips):
        raise AssertionError("the chip was asked for")

    cfg = tiny_ids.qwen3_next()
    cfg["program"]["zoo"] = "NoSuchModel"
    cell = ids_cell(cfg)
    monkeypatch.setattr(harness, "load_cell", lambda name: cell)
    monkeypatch.setattr(harness, "require_chips", no_chip)
    with pytest.raises(SystemExit, match="cannot build zoo.NoSuchModel"):
        run_mod.main(["--workload", "tiny", "--seed", "1", "--seconds", "1"])
    harness.require_model({"input": {}})                 # a configuration that names no program


def kernel(name, start, dur):
    return ev(f"%{name}.1 = f32[8]{{0}} custom-call(f32[8]{{0}} %p), custom_call_target=\"tpu_custom_call\"",
              start, dur)


def test_device_ops_lists_every_kernel_family_whatever_its_rank():
    """Twelve fusions of 100 ns each and four kernel families of 1 to 4 ns:
    ten rows, the four families among them."""
    ops = [ev(f"%fusion.{i} = f32[{i + 1},8]{{1,0}} fusion(f32[8]{{0}} %p), kind=kLoop", 200 * i, 100)
           for i in range(12)]
    names = ["dl4j_convsilu_fwd_n128_r1_h48_c64_d128_bfloat16", "jvp_dl4j_gdn_fwd_n128_r1_h32k16_c64",
             "transpose_jvp_dl4j_gdn_bwd_n128_r1_h32k16_c64", "dl4j_rope_fwd_bh48_t8192_d128_r128"]
    ops += [kernel(n, 3000 + 10 * i, i + 1) for i, n in enumerate(names)]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                          NS(name="XLA Modules", events=[ev("jit_step(1)", 0, 4000)])])
    rows = tr.reduce_planes([dev], 1).device_ops()
    assert len(rows) == 10
    labels = [k for k, _ in rows]
    assert {"dl4j_convsilu_fwd", "dl4j_gdn_fwd", "dl4j_gdn_bwd", "dl4j_rope_fwd"} <= set(labels)
    assert [v for _, v in rows] == sorted((v for _, v in rows), reverse=True)
    assert sum(1 for k in labels if k.startswith("fusion")) == 6


def test_idle_gaps_can_name_the_programs_spans():
    assert tr.HOST_NAMES.match("dl4j.score_wait") and tr.HOST_NAMES.match("dl4j.dispatch")
    assert tr.HOST_NAMES.match("bench.next_batch") and not tr.HOST_NAMES.match("SomethingElse")
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("%fusion.1 = f32[8]{0} fusion()", 0, 100),
                                   ev("%fusion.2 = f32[8]{0} fusion()", 900, 100)]),
        NS(name="XLA Modules", events=[ev("jit_step(1)", 0, 100), ev("jit_step(1)", 900, 100)])])
    host = NS(name="/host:CPU", lines=[NS(name="fit", events=[
        ev("dl4j.step", 0, 1000), ev("dl4j.score_wait", 200, 600)])])
    gaps = dict(tr.reduce_planes([dev, host], 1).idle_gaps_by_host())
    assert gaps["dl4j.score_wait"] == pytest.approx(600e-9)
    assert gaps["dl4j.step"] == pytest.approx(800e-9)            # it holds the wait: not exclusive
