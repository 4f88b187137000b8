"""The traffic generators and the cell loops, rehearsed on the CPU at tiny
sizes through the same functions a chip run calls."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness
from benchmark.tests import tiny
from benchmark.traffic import serve_open_loop as so
from benchmark.traffic import train_stream as ts

ROOT = harness.ROOT
BIG_SEED = 2 ** 31 + 12345


def serve_traffic(**over):
    with open(os.path.join(ROOT, "benchmark", "traffic", "serve_mix.json")) as f:
        t = json.load(f)
    t.update(over)
    return t


def test_schedule_is_the_same_work_in_another_order():
    t = serve_traffic()
    a = so.make_schedule(t, 1, 10.0, rate=200)
    b = so.make_schedule(t, BIG_SEED, 10.0, rate=200)
    assert len(a[0]) == len(b[0]) == 2000
    assert sorted(a[1]) == sorted(b[1]) and list(a[1]) != list(b[1])
    gaps = [np.sort(np.diff(np.append(s[0], 10.0))) for s in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=0, atol=1e-9)
    assert a[0][0] == 0 and a[0][-1] < 10.0
    shares = {k: float(np.mean(a[1] == k)) for k in (1, 2, 4, 8, 16)}
    assert shares == pytest.approx({1: .6, 2: .15, 4: .15, 8: .08, 16: .02}, abs=1e-3)
    again = so.make_schedule(t, BIG_SEED, 10.0, rate=200)
    assert all(np.array_equal(x, y) for x, y in zip(b, again))


def test_batches_are_seeded_one_hot_and_all_rows_differ():
    cfg = tiny.gpt2()
    t = {"distinct_batches": 3}
    a = ts.make_batches(cfg, t, 4, BIG_SEED)
    b = ts.make_batches(cfg, t, 4, BIG_SEED)
    for (x, y, idx), (x2, y2, _) in zip(a, b):
        assert np.array_equal(x, x2) and np.array_equal(y, y2)
        assert x.dtype == np.int32 and y.dtype == np.float32
        assert y.shape == (4, 16, 64) and np.all(y.sum(-1) == 1.0)
        assert np.array_equal(y.argmax(-1), idx)
        assert np.array_equal(idx[:, :-1], x[:, 1:])       # next token
    rows = np.concatenate([x for x, _, _ in a])
    assert len({r.tobytes() for r in rows}) == len(rows)


def train_cell(cfg):
    return {"name": "tiny", "chips": 1, "cfg": cfg,
            "traffic_params": {"kind": "train_stream", "per_chip_batch": 4,
                               "distinct_batches": 3, "check_steps": 3,
                               "trace_seconds": 1}}


def loose(mod, monkeypatch):
    """The chip's limits are for bf16 at full size; a float32 CPU rehearsal
    of the control flow only needs them finite."""
    monkeypatch.setattr(mod, "LIMITS", dict.fromkeys(mod.LIMITS, 1e-3))


def test_train_cell_loop_on_cpu(monkeypatch):
    out = ts.run(tiny.ctx(train_cell(tiny.gpt2()), seed=BIG_SEED, seconds=0.5))
    assert out["attempted"] > 3 and out["failed"] == 0
    assert out["values"]["train_throughput"] > 0
    names = [r[0] for r in out["checks"]]
    assert names[:5] == ["loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
                         "grad_norm_gap", "delta_norm_gap"]
    # float32 program against the float32 reference: rounding only
    assert all(r[1] < 1e-4 for r in out["checks"][:5]), out["checks"]
    assert dict((r[0], r[1]) for r in out["checks"])["compiles_in_window"] == 0


def test_serve_cell_loop_on_cpu():
    t = serve_traffic(rate_rps=30, pool_rows=32, calibration_rows=16,
                      checked_requests=16, warm_seconds=0.3, deadline_s=5.0)
    cell = {"name": "tiny", "chips": 1, "cfg": tiny.resnet50(),
            "traffic_params": t}
    out = so.run(tiny.ctx(cell, seed=BIG_SEED, seconds=1.5))
    assert out["attempted"] == 45
    gap = out["checks"][0]
    assert gap[0] == "answer_gap" and gap[1] < 1e-2, gap   # f32 vs f32
    assert out["counters"]["batches"] > 0
    assert out["counters"]["rows_answered"] > 0


def test_run_py_refuses_to_run_off_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "gpt2s_train_t1024",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs 1 TPU chip" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_every_cell_finds_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        kind = cell["traffic_params"]["kind"]
        assert os.path.exists(os.path.join(ROOT, "benchmark", "traffic", kind + ".py"))
        for key in ("reference", "flops"):
            harness.module(key, cell["cfg"][key])
        assert {"setup_s"} < {m["name"] for m in cell["end_to_end"]}
        assert cell["per_layer"]
        for m in cell["per_layer"]:
            assert callable(harness.module("metrics", m["name"]).read)
