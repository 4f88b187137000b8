"""`train_stream_ids` over the tiny `laguna` configuration on the CPU: whole
runs (run.py's main, with only the look for a chip skipped) print
`"correct": true` over the sound program and false over each broken path —
sliding layers that see the whole past, a window one key short, global layers
turned at the plain frequencies, tables without their factor, heads without
their gate, half a batch left out, a buffer that overflows — and with each of
the reference's controls in the program's place."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from benchmark import harness
from benchmark.reference import common
from benchmark.tests import tiny_ids, tiny_laguna
from benchmark.tests.test_correct import SEED, break_step, run_main
from benchmark.tests.test_train_stream_ids import cell, failed
from benchmark.traffic import train_stream_ids as tsi
from deeplearning4j_tpu.nn.layers import hybrid
from deeplearning4j_tpu.ops import attention as att


def test_sound_run_is_correct(monkeypatch, capsys):
    result, out = run_main(monkeypatch, capsys, cell(tiny_laguna.laguna()))
    assert result["correct"] is True, out
    assert set(result["metrics"]) == {"train_throughput", "setup_s"}
    assert "[check] expert_dropped_assignments = 0 limit 0 ok" in out


def broken(monkeypatch, capsys):
    result, out = run_main(monkeypatch, capsys, cell(tiny_laguna.laguna()))
    assert result["correct"] is False, out
    assert any("gap" in name for name in failed(out)), out
    print("\n".join(l for l in out.splitlines() if l.startswith("[check]")))


def with_window(monkeypatch, change):
    real = att.attend

    def attend(q, k, v, *, window=None, **kw):
        return real(q, k, v, window=change(window), **kw)

    monkeypatch.setattr(att, "attend", attend)


def test_sliding_layers_without_their_window_are_not_correct(monkeypatch, capsys):
    with_window(monkeypatch, lambda w: None)
    broken(monkeypatch, capsys)


def test_a_window_one_key_short_is_not_correct(monkeypatch, capsys):
    with_window(monkeypatch, lambda w: w and w - 1)
    broken(monkeypatch, capsys)


def test_global_layers_at_the_plain_frequencies_are_not_correct(monkeypatch, capsys):
    real = hybrid.frequencies
    monkeypatch.setattr(hybrid, "frequencies", lambda rot, theta, scaling=None, j=None: (
        real(rot, theta, None, j)[0], real(rot, theta, scaling, j)[1]))
    broken(monkeypatch, capsys)


def test_tables_without_their_factor_are_not_correct(monkeypatch, capsys):
    real = hybrid.frequencies
    monkeypatch.setattr(hybrid, "frequencies", lambda rot, theta, scaling=None, j=None: (
        real(rot, theta, scaling, j)[0], 1.0))
    broken(monkeypatch, capsys)


def test_heads_without_their_gate_are_not_correct(monkeypatch, capsys):
    real = hybrid.GatedAttention.apply

    def apply(self, params, x, **kw):
        return real(dataclasses.replace(self, gated=False),
                    {k: v for k, v in params.items() if k != "Wg"}, x, **kw)

    monkeypatch.setattr(hybrid.GatedAttention, "apply", apply)
    broken(monkeypatch, capsys)


def test_half_a_batch_left_out_is_not_correct(monkeypatch, capsys):
    def half(real):
        def step(params, state, opt_state, it, rng, x, y, fm, lm):
            h = x.shape[0] // 2
            return real(params, state, opt_state, it, rng, jnp.concatenate([x[:h], x[:h]]),
                        jnp.concatenate([y[:h], y[:h]]), fm, lm)
        return step

    break_step(monkeypatch, half)
    broken(monkeypatch, capsys)


def test_dropped_assignments_are_not_correct(monkeypatch, capsys):
    cfg = tiny_laguna.laguna()
    cfg["program"]["args"]["capacity_factor"] = 0.5
    result, out = run_main(monkeypatch, capsys, cell(cfg))
    assert result["correct"] is False
    assert "expert_dropped_assignments" in failed(out), out


def numbers(cfg, operand=None):
    ref = harness.module("reference", cfg["reference"])
    batches = tsi.make_batches(cfg, tiny_ids.TRAIN_IDS, 2, SEED)
    p0 = jax.device_get(ref.init_params(cfg, SEED))
    return ref, tsi.reference_numbers(ref, cfg, p0, {}, batches, 3, operand)


@pytest.fixture(scope="module")
def want():
    return numbers(tiny_laguna.laguna())


@pytest.mark.parametrize("control", ["float8_e4m3fn", "drop_window", "window_511", "drop_yarn",
                                     "drop_rope_scale", "drop_gate"])
def test_the_controls_come_out_not_correct(control, want):
    ref, sound = want
    assert control == ref.CONTROL or control in ref.CONTROLS
    _, ctl = numbers(tiny_laguna.laguna(), control)
    rows = common.compare_training(ctl, sound, ref.LIMITS, ref.COMPARISONS)
    assert not all(r[3] for r in rows), rows
    same = common.compare_training(sound, sound, ref.LIMITS, ref.COMPARISONS)
    assert all(r[3] for r in same)
