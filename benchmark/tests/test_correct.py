"""`correct` can fail, and fails for the right reasons.

  * the control — the reference with its operands rounded to
    float8_e4m3fn — put in the program's place at a tiny size, comes out
    not correct under the limits the chip readings set;
  * a whole run (run.py's main, with only the look for a chip skipped) over
    a timed path broken underneath — a step that returns its state
    unchanged, a step that leaves out half the batch, a served answer
    altered where it is produced — prints `"correct": false`; the same run
    over the sound path prints true.
"""
import json
import os
import sys

import numpy as np
import pytest

from benchmark import harness, program
from benchmark.reference import common
from benchmark.tests import tiny
from benchmark.traffic import train_stream as ts

sys.path.insert(0, os.path.join(harness.ROOT, "benchmark"))
import run as run_mod  # noqa: E402

SEED = 2 ** 31 + 77
TRAIN = {"kind": "train_stream", "per_chip_batch": 4, "distinct_batches": 3,
         "check_steps": 3, "trace_seconds": 1}


def tiny_cell(cfg, traffic):
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    kind = "train" if traffic["kind"] == "train_stream" else "serve"
    e2e = [m for m in bench["end_to_end"]
           if m["name"] == "setup_s" or kind in m["name"]]
    if kind == "serve":     # no served cell in BENCHMARK.json yet (PERF.md section 7)
        e2e.append({"name": "serve_goodput", "unit": "rows/s", "better": "higher",
                    "bound": 0.01, "source": "host_clock"})
    return {"name": "tiny", "chips": 1, "cfg": cfg, "traffic_params": traffic,
            "end_to_end": e2e, "per_layer": []}


def run_main(monkeypatch, capsys, cell, seconds="0.5"):
    """run.py's main on the CPU: everything but the look for a chip."""
    monkeypatch.setattr(harness, "load_cell", lambda name: cell)
    monkeypatch.setattr(harness, "require_chips", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: 0)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    assert run_mod.main(["--workload", "tiny", "--seed", str(SEED),
                         "--seconds", seconds, "--trace", "0"]) == 0
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


def test_the_training_control_comes_out_not_correct():
    cfg = tiny.gpt2()
    ref = harness.module("reference", cfg["reference"])
    batches = ts.make_batches(cfg, TRAIN, 8, SEED)
    p0, s0 = ref.init_params(cfg, SEED), ref.init_state(cfg, SEED)
    want = ts.reference_numbers(ref, cfg, p0, s0, batches, 3)
    ctl = ts.reference_numbers(ref, cfg, p0, s0, batches, 3,
                               operand=ref.CONTROL)
    rows = common.compare_training(ctl, want, ref.LIMITS, ref.COMPARISONS)
    assert not all(r[3] for r in rows), rows
    same = common.compare_training(want, want, ref.LIMITS, ref.COMPARISONS)
    assert all(r[3] for r in same) and all(r[1] == 0 for r in same)


def test_the_serving_control_comes_out_not_correct():
    import jax

    from benchmark.traffic import serve_open_loop as so

    cfg = tiny.resnet50()
    ref = harness.module("reference", cfg["reference"])
    pool = so.make_pool(cfg, {"pool_rows": 32}, SEED)
    params = ref.init_params(cfg, SEED)
    state = jax.jit(lambda p, x: ref.calibrated_state(p, x, cfg))(params, pool[:16])
    want = so.reference_pool_logits(ref, cfg, params, state, pool)
    ctl = so.reference_pool_logits(ref, cfg, params, state, pool, ref.CONTROL)
    served = np.exp(ctl - ctl.max(-1, keepdims=True))
    served /= served.sum(-1, keepdims=True)
    assert so.answer_gap(served, want) > ref.LIMITS["answer_gap"]
    exact = np.exp(want - want.max(-1, keepdims=True))
    exact /= exact.sum(-1, keepdims=True)
    assert so.answer_gap(exact, want) < 1e-5


def test_sound_run_is_correct(monkeypatch, capsys):
    result, out = run_main(monkeypatch, capsys, tiny_cell(tiny.gpt2(), TRAIN))
    assert result["correct"] is True, out
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(result["metrics"]) == {"train_throughput", "setup_s"}
    assert result["metrics"]["train_throughput"]["unit"] == "examples/s"
    assert out.count("[check]") >= 7           # every number beside its limit


def break_step(monkeypatch, make_broken):
    real_build = program.build_net

    def build(cfg):
        net = real_build(cfg)
        net._train_step = make_broken(net._build_train_step())
        return net

    monkeypatch.setattr(program, "build_net", build)


def test_step_that_returns_its_state_unchanged(monkeypatch, capsys):
    def unchanged(real):
        def step(params, state, opt_state, it, rng, x, y, fm, lm):
            import jax.numpy as jnp

            copy = lambda t: __import__("jax").tree_util.tree_map(jnp.copy, t)  # noqa: E731
            _, _, _, score = real(copy(params), copy(state), copy(opt_state),
                                  it, rng, x, y, fm, lm)
            return params, state, opt_state, score
        return step

    break_step(monkeypatch, unchanged)
    result, out = run_main(monkeypatch, capsys, tiny_cell(tiny.gpt2(), TRAIN))
    assert result["correct"] is False
    assert "delta_norm_gap = 1.0" in out and "FAIL" in out


def test_step_that_leaves_out_half_the_batch(monkeypatch, capsys):
    def half(real):
        def step(params, state, opt_state, it, rng, x, y, fm, lm):
            import jax.numpy as jnp

            h = x.shape[0] // 2
            return real(params, state, opt_state, it, rng,
                        jnp.concatenate([x[:h], x[:h]]),
                        jnp.concatenate([y[:h], y[:h]]), fm, lm)
        return step

    break_step(monkeypatch, half)
    result, out = run_main(monkeypatch, capsys, tiny_cell(tiny.gpt2(), TRAIN))
    assert result["correct"] is False
    failed = [l for l in out.splitlines() if l.startswith("[check]") and "FAIL" in l]
    assert any("loss_gap" in l for l in failed), out


def test_served_answer_altered_where_it_is_produced(monkeypatch, capsys):
    with open(os.path.join(harness.ROOT, "benchmark", "traffic", "serve_mix.json")) as f:
        traffic = json.load(f)
    traffic.update(rate_rps=30, pool_rows=32, calibration_rows=16,
                   checked_requests=16, warm_seconds=0.3, deadline_s=5.0)
    real_server = program.server

    def server(net, mesh, traffic, warm):
        s = real_server(net, mesh, traffic, warm)
        inner = s._dispatch

        def altered(xp):
            out = np.array(inner(xp), np.float32)
            out[:, 0] += 0.5
            return out

        s._dispatch = altered
        return s

    cell = tiny_cell(tiny.resnet50(), traffic)
    sound, out = run_main(monkeypatch, capsys, cell, seconds="1.0")
    assert sound["correct"] is True, out
    assert set(sound["metrics"]) == {"serve_goodput", "setup_s"}
    monkeypatch.setattr(program, "server", server)
    broken, out = run_main(monkeypatch, capsys, cell, seconds="1.0")
    assert broken["correct"] is False and "answer_gap" in out
