"""`train_stream_ids` over the tiny `deepseek_v3` configuration on the CPU:
whole runs (run.py's main, with only the look for a chip skipped) print
`"correct": true` over the sound program and false over each broken path —
a layer that rotates nothing, one that pairs the features the other way, one
held expert's terms left out, the shared experts left out, the selection bias
ignored, half a batch left out, a buffer that overflows — and with each of
the reference's controls in the program's place."""
import jax
import jax.numpy as jnp
import pytest

from benchmark import harness
from benchmark.reference import common
from benchmark.tests import tiny_ids, tiny_kanana
from benchmark.tests.test_correct import SEED, break_step, run_main
from benchmark.tests.test_train_stream_ids import cell, failed
from benchmark.traffic import train_stream_ids as tsi
from deeplearning4j_tpu.nn.layers import hybrid


def test_sound_run_is_correct(monkeypatch, capsys):
    result, out = run_main(monkeypatch, capsys, cell(tiny_kanana.kanana()))
    assert result["correct"] is True, out
    assert set(result["metrics"]) == {"train_throughput", "setup_s"}
    assert "[check] expert_dropped_assignments = 0 limit 0 ok" in out


def broken(monkeypatch, capsys):
    result, out = run_main(monkeypatch, capsys, cell(tiny_kanana.kanana()))
    assert result["correct"] is False, out
    assert any("gap" in name for name in failed(out)), out
    print("\n".join(l for l in out.splitlines() if l.startswith("[check]")))


def test_a_layer_that_rotates_nothing_is_not_correct(monkeypatch, capsys):
    monkeypatch.setattr(hybrid, "rotary", lambda x, *a, **kw: x)
    broken(monkeypatch, capsys)


def test_the_other_pairing_is_not_correct(monkeypatch, capsys):
    real = hybrid.rotary
    monkeypatch.setattr(hybrid, "rotary", lambda x, dim, theta, start=0, interleave=False:
                        real(x, dim, theta, start, not interleave))
    broken(monkeypatch, capsys)


def test_one_experts_terms_left_out_is_not_correct(monkeypatch, capsys):
    real = hybrid.RoutedExperts.route

    def route(self, params, xf):
        top, idx = real(self, params, xf)
        first, _ = self.held()
        return jnp.where(idx == first, 0.0, top), idx

    monkeypatch.setattr(hybrid.RoutedExperts, "route", route)
    broken(monkeypatch, capsys)


def test_the_shared_experts_left_out_is_not_correct(monkeypatch, capsys):
    real = hybrid.RoutedExperts.apply

    def apply(self, params, x, **kw):
        return real(self, dict(params, shared_Wd=jnp.zeros_like(params["shared_Wd"])), x, **kw)

    monkeypatch.setattr(hybrid.RoutedExperts, "apply", apply)
    broken(monkeypatch, capsys)


def test_the_selection_bias_ignored_is_not_correct(monkeypatch, capsys):
    real = hybrid.RoutedExperts.route

    def route(self, params, xf):
        return real(self, dict(params, select_bias=jnp.zeros_like(params["select_bias"])), xf)

    monkeypatch.setattr(hybrid.RoutedExperts, "route", route)
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
    cfg = tiny_kanana.kanana()
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
    return numbers(tiny_kanana.kanana())


@pytest.mark.parametrize("control", ["float8_e4m3fn", "drop_rope", "half_split", "drop_expert",
                                     "drop_shared", "ignore_bias"])
def test_the_controls_come_out_not_correct(control, want):
    ref, sound = want
    _, ctl = numbers(tiny_kanana.kanana(), control)
    rows = common.compare_training(ctl, sound, ref.LIMITS, ref.COMPARISONS)
    assert not all(r[3] for r in rows), rows
    same = common.compare_training(sound, sound, ref.LIMITS, ref.COMPARISONS)
    assert all(r[3] for r in same)
