"""`train_stream_ids` at a tiny size on the CPU: whole runs (run.py's main,
with only the look for a chip skipped) print `"correct": true` over the
sound program, and false over a delta rule that loses the state between
chunks, over an expert layer that leaves one expert's terms out, and with
the float8 control in the program's place; the reference's lean steps are
`common.train_steps`."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import common
from benchmark.tests import tiny, tiny_ids
from benchmark.tests.test_correct import SEED, run_main
from benchmark.traffic import train_stream_ids as tsi
from deeplearning4j_tpu.nn.layers import hybrid


def cell(cfg):
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        e2e = [m for m in json.load(f)["end_to_end"]
               if m["name"] in ("setup_s", "train_throughput")]
    return {"name": "tiny", "chips": 1, "cfg": cfg, "traffic_params": tiny_ids.TRAIN_IDS,
            "end_to_end": e2e, "per_layer": []}


def failed(out):
    return [l.split()[1] for l in out.splitlines()
            if l.startswith("[check]") and "FAIL" in l]


def test_sound_run_is_correct(monkeypatch, capsys):
    result, out = run_main(monkeypatch, capsys, cell(tiny_ids.qwen3_next()))
    assert result["correct"] is True, out
    assert set(result["metrics"]) == {"train_throughput", "setup_s"}
    assert "[check] expert_dropped_assignments = 0 limit 0 ok" in out
    # every run says what its host did during the window, and where its steps went
    host = next(l for l in out.splitlines() if l.startswith("[bench] window host"))
    steps = next(l for l in out.splitlines() if l.startswith("[bench] window steps"))
    assert "ticker_longest_stop_s" in host and "gc_passes" in host
    assert "'score_wait'" in steps and "longest_s" in steps
    print(host, steps, sep="\n")


def test_gpt2_runs_on_integer_labels_with_its_own_reference(monkeypatch, capsys):
    result, out = run_main(monkeypatch, capsys, cell(tiny.gpt2()))
    assert result["correct"] is True, out
    assert "expert_dropped_assignments" not in out


def test_a_scan_that_loses_its_carry_is_not_correct(monkeypatch, capsys):
    step = hybrid._chunk_step
    monkeypatch.setattr(hybrid, "_chunk_step",
                        lambda s, ab: step(jnp.zeros_like(s), ab))
    result, out = run_main(monkeypatch, capsys, cell(tiny_ids.qwen3_next()))
    assert result["correct"] is False
    assert any("gap" in name for name in failed(out)), out


def test_one_experts_terms_left_out_is_not_correct(monkeypatch, capsys):
    real = hybrid.RoutedExperts.route

    def route(self, params, xf):
        top, idx = real(self, params, xf)
        first, _ = self.held()
        return jnp.where(idx == first, 0.0, top), idx

    monkeypatch.setattr(hybrid.RoutedExperts, "route", route)
    result, out = run_main(monkeypatch, capsys, cell(tiny_ids.qwen3_next()))
    assert result["correct"] is False
    assert any("gap" in name for name in failed(out)), out


def test_dropped_assignments_are_not_correct(monkeypatch, capsys):
    cfg = tiny_ids.qwen3_next()
    cfg["program"]["args"]["capacity_factor"] = 0.5
    result, out = run_main(monkeypatch, capsys, cell(cfg))
    assert result["correct"] is False
    assert "expert_dropped_assignments" in failed(out), out


def numbers(cfg, operand=None):
    ref = harness.module("reference", cfg["reference"])
    batches = tsi.make_batches(cfg, tiny_ids.TRAIN_IDS, 2, SEED)
    p0 = jax.device_get(ref.init_params(cfg, SEED))
    return ref, p0, batches, tsi.reference_numbers(ref, cfg, p0, {}, batches, 3, operand)


@pytest.mark.parametrize("control", ["float8_e4m3fn", "drop_carry", "drop_expert"])
def test_the_controls_come_out_not_correct(control):
    cfg = tiny_ids.qwen3_next()
    ref, _, _, want = numbers(cfg)
    _, _, _, ctl = numbers(cfg, control)
    rows = common.compare_training(ctl, want, ref.LIMITS, ref.COMPARISONS)
    assert not all(r[3] for r in rows), rows


def test_lean_reference_steps_are_the_common_ones():
    cfg = tiny_ids.qwen3_next(seq_len=40)
    ref, p0, batches, lean = numbers(cfg)
    seq = [(b[0], b[2]) for b in batches]
    plain = common.train_steps(ref, cfg, jax.device_put(p0), {}, seq)
    np.testing.assert_allclose(lean["losses"], plain["losses"], rtol=1e-6)
    for key in ("grad_norms", "delta_norms"):
        for leaf, v in plain[key].items():
            assert lean[key][leaf] == pytest.approx(v, rel=1e-4, abs=1e-9), (key, leaf)


def test_batches_are_integers_from_the_seed():
    cfg = tiny_ids.qwen3_next()
    a = tsi.make_batches(cfg, tiny_ids.TRAIN_IDS, 2, SEED)
    b = tsi.make_batches(cfg, tiny_ids.TRAIN_IDS, 2, SEED)
    c = tsi.make_batches(cfg, tiny_ids.TRAIN_IDS, 2, SEED + 1)
    assert len(a) == 3 and a[0][0].dtype == a[0][1].dtype == np.int32
    assert a[0][0].shape == a[0][1].shape == (2, 80)
    assert all((x[0] == y[0]).all() for x, y in zip(a, b))
    assert not (a[0][0] == c[0][0]).all()
    assert (a[0][1][:, :-1] == a[0][0][:, 1:]).all() and a[0][0].max() < 48


def test_a_program_without_the_model_stops_at_once():
    cfg = tiny_ids.qwen3_next()
    cfg["program"]["zoo"] = "NoSuchModel"
    with pytest.raises(SystemExit):
        harness.require_model(cfg)
    cfg = tiny_ids.qwen3_next()
    cfg["program"]["args"]["no_such_argument"] = 1          # the class is there, not this model
    with pytest.raises(SystemExit):
        harness.require_model(cfg)
    harness.require_model(tiny_ids.qwen3_next())            # builds: no array, no device
