"""`train_stream_ids` over the tiny `ouro` configuration on the CPU: whole runs
(run.py's main, with only the look for a chip skipped) print `"correct": true`
over the sound program and false over each broken path — the passes' states
handed to the loss in the wrong order, an exit distribution whose last pass
does not take the remainder, a gate no gradient reaches, a loop that feeds the
next pass the un-normed state, half a batch left out — and with each of the
reference's controls in the program's place."""
import jax
import jax.numpy as jnp
import pytest

from benchmark import harness
from benchmark.reference import common
from benchmark.tests import tiny_ouro
from benchmark.tests.test_correct import SEED, break_step, run_main
from benchmark.tests.test_train_stream_ids import cell, failed
from benchmark.traffic import train_stream_ids as tsi
from deeplearning4j_tpu.nn.layers import blocks, hybrid, output


def test_sound_run_is_correct(monkeypatch, capsys):
    result, out = run_main(monkeypatch, capsys, cell(tiny_ouro.ouro()))
    assert result["correct"] is True, out
    assert set(result["metrics"]) == {"train_throughput", "setup_s"}
    assert "expert_dropped_assignments" not in out          # no experts: no such check


def broken(monkeypatch, capsys):
    result, out = run_main(monkeypatch, capsys, cell(tiny_ouro.ouro()))
    assert result["correct"] is False, out
    assert any("gap" in name for name in failed(out)), out
    print("\n".join(l for l in out.splitlines() if l.startswith("[check]")))


def test_passes_in_the_wrong_order_are_not_correct(monkeypatch, capsys):
    real = blocks.LoopedStack.apply

    def apply(self, params, x, **kw):
        y, state = real(self, params, x, **kw)
        return y[:, ::-1], state

    monkeypatch.setattr(blocks.LoopedStack, "apply", apply)
    broken(monkeypatch, capsys)


def test_a_last_pass_without_the_remainder_is_not_correct(monkeypatch, capsys):
    def pdf(lam):   # every pass lam_s prod_{j<s}(1 - lam_j): the mass does not sum to one
        stay = jnp.cumprod(1.0 - lam[..., :-1, :], axis=-2)
        return lam * jnp.concatenate([jnp.ones_like(lam[..., :1, :]), stay], axis=-2)

    monkeypatch.setattr(output, "exit_pdf", pdf)
    broken(monkeypatch, capsys)


def test_a_detached_gate_is_not_correct(monkeypatch, capsys):
    real = output.exit_pdf
    monkeypatch.setattr(output, "exit_pdf", lambda lam: jax.lax.stop_gradient(real(lam)))
    broken(monkeypatch, capsys)


def test_a_final_norm_outside_the_loop_is_not_correct(monkeypatch, capsys):
    """The next pass reads the UN-normed state; the head still reads the
    normed one (the reference's `norm_outside` control, in the program)."""
    real = blocks.LoopedStack.apply

    def apply(self, params, x, *, state, train, rng, mask=None):
        inner = blocks.LoopedStack(layers=self.layers[:-1], steps=1)
        norm, w = self.layers[-1], params[str(len(self.layers) - 1)]
        outs = []
        for _ in range(self.steps):
            x = real(inner, params, x, state=state, train=train, rng=rng, mask=mask)[0][:, 0]
            outs.append(hybrid.rms_norm(x, w["w"], norm.eps, norm.zero_centered))
        return jnp.stack(outs, axis=1), state

    monkeypatch.setattr(blocks.LoopedStack, "apply", apply)
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


def numbers(cfg, operand=None):
    ref = harness.module("reference", cfg["reference"])
    batches = tsi.make_batches(cfg, tiny_ouro.TRAIN_IDS, 2, SEED)
    p0 = jax.device_get(ref.init_params(cfg, SEED))
    return ref, tsi.reference_numbers(ref, cfg, p0, {}, batches, 3, operand)


@pytest.fixture(scope="module")
def want():
    return numbers(tiny_ouro.ouro())


@pytest.mark.parametrize("control", ["float8_e4m3fn", "three_passes", "last_pass_loss",
                                     "norm_outside"])
def test_the_controls_come_out_not_correct(control, want):
    ref, sound = want
    _, ctl = numbers(tiny_ouro.ouro(), control)
    rows = common.compare_training(ctl, sound, ref.LIMITS, ref.COMPARISONS)
    assert not all(r[3] for r in rows), rows
    same = common.compare_training(sound, sound, ref.LIMITS, ref.COMPARISONS)
    assert all(r[3] for r in same)


def test_lean_reference_steps_are_the_common_ones():
    """The reference's own `train_steps` (Adam a leaf at a time, the moments on
    the host between steps) against `common.train_steps`."""
    import numpy as np

    cfg = tiny_ouro.ouro(seq_len=40)
    ref = harness.module("reference", cfg["reference"])
    batches = tsi.make_batches(cfg, tiny_ouro.TRAIN_IDS, 2, SEED)
    p0 = jax.device_get(ref.init_params(cfg, SEED))
    lean = tsi.reference_numbers(ref, cfg, p0, {}, batches, 3)
    plain = common.train_steps(ref, cfg, jax.device_put(p0), {}, [(b[0], b[2]) for b in batches])
    np.testing.assert_allclose(lean["losses"], plain["losses"], rtol=1e-6)
    for key in ("grad_norms", "delta_norms"):
        for leaf, v in plain[key].items():
            assert lean[key][leaf] == pytest.approx(v, rel=1e-4, abs=1e-9), (key, leaf)
