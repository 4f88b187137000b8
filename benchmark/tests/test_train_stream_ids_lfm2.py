"""`train_stream_ids` over the tiny `lfm2_moe` configuration on the CPU:
whole runs (run.py's main, with only the look for a chip skipped) print
`"correct": true` over the sound program and false over each broken path —
a convolution that keeps its current-token tap alone, a split read as
[C | B | z], an attention layer that rotates nothing, one held expert's terms
left out, half a batch left out, a buffer that overflows — and with each of
the reference's controls in the program's place."""
import jax
import jax.numpy as jnp
import pytest

from benchmark import harness
from benchmark.reference import common
from benchmark.tests import tiny_ids, tiny_lfm2
from benchmark.tests.test_correct import SEED, break_step, run_main
from benchmark.tests.test_train_stream_ids import cell, failed
from benchmark.traffic import train_stream_ids as tsi
from deeplearning4j_tpu.nn.layers import hybrid


def test_sound_run_is_correct(monkeypatch, capsys):
    result, out = run_main(monkeypatch, capsys, cell(tiny_lfm2.lfm2()))
    assert result["correct"] is True, out
    assert set(result["metrics"]) == {"train_throughput", "setup_s"}
    assert "[check] expert_dropped_assignments = 0 limit 0 ok" in out


def broken(monkeypatch, capsys):
    result, out = run_main(monkeypatch, capsys, cell(tiny_lfm2.lfm2()))
    assert result["correct"] is False, out
    assert any("gap" in name for name in failed(out)), out
    print("\n".join(l for l in out.splitlines() if l.startswith("[check]")))


def test_a_convolution_of_its_last_tap_alone_is_not_correct(monkeypatch, capsys):
    monkeypatch.setattr(hybrid, "short_conv", lambda u, w: u * w[-1])
    broken(monkeypatch, capsys)


def test_the_split_read_as_c_b_z_is_not_correct(monkeypatch, capsys):
    real = hybrid.GatedShortConv.apply

    def apply(self, params, x, **kw):
        b_, c_, z = jnp.split(params["Win"], 3, axis=-1)
        return real(self, dict(params, Win=jnp.concatenate([c_, b_, z], axis=-1)), x, **kw)

    monkeypatch.setattr(hybrid.GatedShortConv, "apply", apply)
    broken(monkeypatch, capsys)


def test_a_layer_that_rotates_nothing_is_not_correct(monkeypatch, capsys):
    monkeypatch.setattr(hybrid, "rotary", lambda x, *a, **kw: x)
    broken(monkeypatch, capsys)


def test_one_experts_terms_left_out_is_not_correct(monkeypatch, capsys):
    real = hybrid.RoutedExperts.route

    def route(self, params, xf):
        top, idx = real(self, params, xf)
        first, _ = self.held()
        return jnp.where(idx == first, 0.0, top), idx

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
    cfg = tiny_lfm2.lfm2()
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
    return numbers(tiny_lfm2.lfm2())


@pytest.mark.parametrize("control", ["float8_e4m3fn", "drop_taps", "swap_bc", "drop_rope",
                                     "drop_expert"])
def test_the_controls_come_out_not_correct(control, want):
    ref, sound = want
    _, ctl = numbers(tiny_lfm2.lfm2(), control)
    rows = common.compare_training(ctl, sound, ref.LIMITS, ref.COMPARISONS)
    assert not all(r[3] for r in rows), rows
    same = common.compare_training(sound, sound, ref.LIMITS, ref.COMPARISONS)
    assert all(r[3] for r in same)
