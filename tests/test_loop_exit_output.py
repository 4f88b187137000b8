"""`LoopExitOutput` (nn/layers/output.py): the head and the exit-weighted
loss over the passes of a looped stack. The exit distribution sums to one and
equals its closed form; a gate at -inf / +inf gives the last / first pass's
loss; `p log p` at p = 0 is finite in value and gradient; masks and integer
labels; the score and its gradients (head, gate, states) against `jax.grad`
of a ten-line float32 formula; the counters a fit reports; what it cannot
take is refused by name."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.layers import LoopExitOutput
from deeplearning4j_tpu.nn.layers.output import entropy, exit_pdf

B, S, T, F, V = 2, 4, 12, 16, 9
IN = it.RecurrentPasses(F, T, passes=S)


def layer(**kw):
    return LoopExitOutput(n_out=V, loss="mcxent", activation="softmax", has_bias=False, **kw)


def formula(params, x, labels, beta, mask=None):
    """The equations of the docstring, plainly, in float32."""
    logp = jax.nn.log_softmax(jnp.einsum("bstf,fv->bstv", x, params["W"]), axis=-1)
    l = -jnp.take_along_axis(logp, jnp.broadcast_to(labels[:, None, :, None], (B, S, T, 1)),
                             axis=-1)[..., 0]                                # [b, s, t]
    lam = jax.nn.sigmoid(jnp.einsum("bstf,f->bst", x, params["gate"]["w"]) + params["gate"]["b"])
    p = [lam[:, s] * jnp.prod(1 - lam[:, :s], axis=1) for s in range(S - 1)]
    p = jnp.stack(p + [jnp.prod(1 - lam[:, :S - 1], axis=1)], axis=1)
    h = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0), axis=1)
    per = jnp.sum(p * l, axis=1) - beta * h                                  # [b, t]
    if mask is None:
        return per.mean()
    return jnp.sum(per * mask) / jnp.sum(mask)


@pytest.fixture
def case(rng):
    out = layer(beta=0.1)
    params = out.init_params(jax.random.PRNGKey(0), IN)
    params["gate"] = {"w": jnp.asarray(rng.normal(size=(F,)) * 0.3, jnp.float32),
                      "b": jnp.asarray(0.2, jnp.float32)}
    x = jnp.asarray(rng.normal(size=(B, S, T, F)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
    return out, params, x, labels, out.init_state(IN)


def test_the_pdf_sums_to_one_and_is_the_closed_form(rng):
    lam = jnp.asarray(rng.uniform(0.05, 0.95, (B, S, T)), jnp.float32)
    p = exit_pdf(lam)
    np.testing.assert_allclose(p.sum(axis=1), 1.0, rtol=1e-6)
    for s in range(S):
        want = np.prod(1 - np.asarray(lam[:, :s]), axis=1) * (lam[:, s] if s < S - 1 else 1.0)
        np.testing.assert_allclose(p[:, s], want, rtol=1e-6)
    # one pass: all the mass, whatever the gate says
    np.testing.assert_array_equal(exit_pdf(lam[:, :1]), jnp.ones((B, 1, T)))


@pytest.mark.parametrize("bias, taken", [(-np.inf, S - 1), (np.inf, 0), (-40.0, S - 1), (40.0, 0)])
def test_a_saturated_gate_gives_one_passes_loss(bias, taken, case):
    out, params, x, labels, state = case
    params = dict(params, gate={"w": jnp.zeros((F,), jnp.float32),
                                "b": jnp.asarray(bias, jnp.float32)})

    def score(p, a):
        return out.compute_loss(p, a, labels, state=state)[0]

    got, (gp, gx) = jax.value_and_grad(score, argnums=(0, 1))(params, x)
    one = dict(params, gate={"w": jnp.zeros((F,), jnp.float32), "b": jnp.asarray(0.0, jnp.float32)})
    want = formula(one, x[:, taken:taken + 1].repeat(S, axis=1), labels, beta=0.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # p log p at p = 0: finite in value and in every gradient; only the pass
    # taken moves the score
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves((gp, gx)))
    others = jnp.delete(gx, taken, axis=1)
    assert float(jnp.abs(others).max()) < 1e-12 and float(jnp.abs(gx[:, taken]).max()) > 1e-6


def test_entropy_at_zero_is_zero_in_value_and_gradient():
    p = jnp.asarray([[0.0, 1.0, 0.0], [0.25, 0.5, 0.25]], jnp.float32)
    value, grad = jax.value_and_grad(lambda q: entropy(q, axis=1).sum())(p)
    np.testing.assert_allclose(value, 1.5 * np.log(2.0), rtol=1e-6)
    assert bool(jnp.all(jnp.isfinite(grad)))
    np.testing.assert_array_equal(grad[0, ::2], 0.0)


@pytest.mark.parametrize("masked", [False, True])
def test_score_and_gradients_are_the_formulas(masked, case, rng):
    out, params, x, labels, state = case
    mask = (jnp.asarray(rng.integers(0, 2, (B, T)), jnp.float32).at[:, 0].set(1.0)
            if masked else None)

    def score(p, a):
        return out.compute_loss(p, a, labels, state=state, mask=mask)[0]

    got, (gp, gx) = jax.value_and_grad(score, argnums=(0, 1))(params, x)
    want, (wp, wx) = jax.value_and_grad(
        lambda p, a: formula(p, a, labels, out.beta, mask), argnums=(0, 1))(params, x)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path((gp, gx)), jax.tree.leaves((wp, wx))):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6 * float(jnp.abs(b).max()),
                                   err_msg=str(path))
    # the gate learns through both terms, the states through the losses and the gate
    assert float(jnp.abs(gp["gate"]["w"]).max()) > 0 and float(jnp.abs(gp["gate"]["b"])) > 0
    assert all(float(jnp.abs(gx[:, s]).max()) > 0 for s in range(S))
    score_, per_ex, _ = out.compute_loss(params, x, labels, state=state, mask=mask)
    assert per_ex.shape == (B, T)
    if masked:
        assert float(jnp.abs(per_ex * (1 - mask)).max()) == 0.0
    # the entropy bonus is beta's: without it the score is the expectation alone
    plain = layer(beta=0.0).compute_loss(params, x, labels, state=state, mask=mask)[0]
    assert float(plain) > float(score_)


def test_output_is_the_last_passes_softmax(case):
    out, params, x, labels, state = case
    y, _ = out.apply(params, x, state=state, train=False, rng=None)
    assert y.shape == (B, T, V)
    np.testing.assert_allclose(y, jax.nn.softmax(x[:, -1] @ params["W"], axis=-1), rtol=1e-5)
    assert out.output_type(IN) == it.recurrent(V, T)


def test_params_and_counters(case):
    out, params, x, labels, state = case
    assert sorted(params) == ["W", "gate"] and sorted(params["gate"]) == ["b", "w"]
    assert params["W"].shape == (F, V) and params["gate"]["w"].shape == (F,)
    assert params["gate"]["b"].shape == ()
    assert sorted(out.regularizable(params)) == ["W", "gate/w"]
    specs = out.tensor_partition_specs(params, model_size=1)
    assert jax.tree.structure(specs, is_leaf=lambda s: not isinstance(s, dict)) \
        == jax.tree.structure(params)
    for _ in range(2):
        _, _, state = out.compute_loss(params, x, labels, state=state)
    c = jax.device_get(state["counters"])
    assert int(c["steps"]) == 2 and c["exit_p"].shape == c["loss_by_pass"].shape == (S,)
    key, entry = out.counter_summary({k: np.atleast_1d(v).astype(np.float64)
                                      for k, v in c.items()})
    assert key == "exit" and entry["steps"] == 2
    np.testing.assert_allclose(sum(entry["exit_p"]), 1.0, rtol=1e-5)
    assert 1.0 < entry["expected_passes"] < S
    assert 0.0 < entry["exit_entropy"] < np.log(S)
    assert all(v > 0 for v in entry["loss_by_pass"])
    assert entry["expected_passes"] == pytest.approx(
        sum((s + 1) * p for s, p in enumerate(entry["exit_p"])))


REFUSED = {
    "dense one-hot labels": lambda x: jax.nn.one_hot(jnp.zeros((B, T), jnp.int32), V),
    "labels of another shape": lambda x: jnp.zeros((B, T + 1), jnp.int32),
    "labels a pass": lambda x: jnp.zeros((B, S, T), jnp.int32),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_it_cannot_take_is_refused_by_name(what, case):
    out, params, x, labels, state = case
    with pytest.raises(TypeError, match=r"LoopExitOutput: passes \[b, steps, t, f\]"):
        out.compute_loss(params, x, REFUSED[what](x), state=state)


def test_states_of_one_pass_alone_are_refused(case):
    out, params, x, labels, state = case
    with pytest.raises(TypeError, match="LoopExitOutput"):
        out.compute_loss(params, x[:, 0], labels, state=state)
    with pytest.raises(TypeError, match="LoopExitOutput"):
        LoopExitOutput(n_out=V, loss="mse", activation="identity").compute_loss(
            params, x, labels, state=state)
