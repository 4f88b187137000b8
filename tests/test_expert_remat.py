"""What a block's 'full' remat keeps of its routed experts (`REMAT_KEEP` in
`RoutedExperts`): `h` = the first grouped product's output within
`hybrid.H_KEEP_BYTES`, the sort's `order` / `inv` / `sizes`, the router's
logits and — under the sigmoid recipe — the chosen ids, so that the block's
recompute neither multiplies the buffer by Wgu, nor sorts, nor scores, nor
(sigmoid) selects again. Counted in the
gradient's jaxpr of two `HybridBlock`s, tagged against untagged, as
tests/test_attention.py does for the flash results and a latent layer's q, k, v."""
import re
import unittest.mock as mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.layers import GatedAttention, HybridBlock, RoutedExperts, hybrid
from deeplearning4j_tpu.parallel.layout import maybe_remat

B, T, F, N_EXPERTS, TOP_K, WIDTH = 2, 64, 32, 8, 2, 24      # no two widths alike
N = B * T
CASES = {
    # expert_act, scoring, whether `h` is within the bound
    "swiglu": ("swiglu", "softmax", True),
    "relu2": ("relu2", "sigmoid", True),
    "h_over_the_bound": ("swiglu", "softmax", False),
}


@pytest.fixture(params=list(CASES))
def expert_stack(request, rng, monkeypatch):
    """Two `HybridBlock`s (attention, then experts), each behind
    `maybe_remat(., policy)`: `loss(policy)` is a function of (params, x);
    the last item = (the first product's [cap, wide * e], whether the recipe
    is relu2 + sigmoid, whether `h` is kept)."""
    act, scoring, within = CASES[request.param]
    moe = RoutedExperts(n_experts=N_EXPERTS, top_k=TOP_K, expert_width=WIDTH, shared_width=WIDTH,
                        expert_act=act, scoring=scoring)
    block = HybridBlock(mixer=GatedAttention(n_heads=2, n_kv_heads=1, head_dim=16), moe=moe)
    itype = it.recurrent(F, T)
    params = [block.init_params(jax.random.PRNGKey(i), itype) for i in range(2)]
    state = block.init_state(itype)
    x = jnp.asarray(rng.standard_normal((B, T, F)), jnp.float32)
    h_shape = (moe.capacity(N), hybrid.EXPERT_ACTS[act][1] * WIDTH)
    if not within:      # one byte under what `h` takes in float32
        monkeypatch.setattr(hybrid, "H_KEEP_BYTES", 4 * h_shape[0] * h_shape[1] - 1)

    def apply(p, h):
        return block.apply(p, h, state=state, train=True, rng=None)[0]

    def loss(params, x, policy):
        for p in params:
            # a function object a trace: a checkpoint's jaxpr is cached by it
            x = maybe_remat(lambda p, h: apply(p, h), policy)(p, x)
        return jnp.sum(x * x)

    return (lambda policy: lambda params, x: loss(params, x, policy)), apply, params, x, \
        (h_shape, act == "relu2", within)


def _untagged():
    """`hybrid` as the commit before the experts' tags had it: nothing named."""
    return mock.patch.object(hybrid, "checkpoint_name", lambda a, name: a)


def _eqns(jaxpr, primitive):
    """Every equation of one primitive in a jaxpr, at any depth, in order."""
    found = []
    for e in jaxpr.eqns:
        if e.primitive.name == primitive:
            found.append(e)
        for inner in jax.core.jaxprs_in_params(e.params):
            found += _eqns(inner, primitive)
    return found


def _made(jaxpr, primitive):
    return [tuple(e.outvars[0].aval.shape) for e in _eqns(jaxpr, primitive)]


def test_full_remat_multiplies_sorts_and_scores_once_a_layer(expert_stack):
    """The gradient of two expert blocks under 'full': the two argsorts and
    the router's [n, n_experts] product once a layer and not again in the
    recompute, under the sigmoid recipe the selection too (its weights' gather
    reruns at the kept ids; the softmax recipe's `top_k` reruns: its
    derivative reads its own ids); the first grouped product [cap, wide * e] once a layer where `h` is
    within the bound, twice beyond it; the second product `act(h) Wd` twice
    either way (the router weights' gradient reads ys). Untagged, each of them
    twice a layer."""
    loss, _, params, x, (h_shape, sigmoid, within) = expert_stack

    def counts():
        jaxpr = jax.make_jaxpr(jax.grad(loss("full")))(params, x).jaxpr
        grouped = _made(jaxpr, "ragged_dot_general")
        return (len(_eqns(jaxpr, "sort")), _made(jaxpr, "dot_general").count((N, N_EXPERTS)),
                len(_eqns(jaxpr, "top_k")), _made(jaxpr, "gather").count((N, TOP_K)),
                grouped.count(h_shape), len(grouped))

    tagged = counts()
    with _untagged():
        untagged = counts()
    # a layer: 2 products forward, 2 in the recompute, 2 + 2 backward; relu2's
    # `d ys Wd^T` [cap, f] x [g, f, e] has the shapes of `xs Wu`
    alike = 2 * sigmoid
    assert untagged == (8, 4, 4, 4 * sigmoid, 4 + alike, 16)
    assert tagged == (4, 2, 2 if sigmoid else 4, 4 * sigmoid,
                      (2 if within else 4) + alike, 14 if within else 16)


def test_full_remat_keeps_h_the_sort_the_logits_and_the_ids(expert_stack, capsys):
    """What a checkpointed expert block keeps for its backward beside its
    arguments: the logits [n, n_experts] float32, `order` and `inv` [top_k n],
    `sizes` [n_experts], under the sigmoid recipe the ids [n, top_k] (and the
    indices `take_along_axis` makes of them) and —
    within the bound — `h`; untagged, its arguments alone."""
    _, apply, params, x, (h_shape, sigmoid, within) = expert_stack

    def kept():
        jax.ad_checkpoint.print_saved_residuals(
            maybe_remat(lambda p, h: apply(p, h), "full"), params[0], x)
        lines = capsys.readouterr().out.strip().splitlines()
        assert any("from the argument h" in ln for ln in lines)
        return sorted(re.sub(r"^[a-z]+\d+", "", ln.split()[0]) for ln in lines
                      if "from the argument" not in ln and "from a constant" not in ln)

    want = [f"[{N},{N_EXPERTS}]", f"[{N_EXPERTS}]"] + [f"[{TOP_K * N}]"] * 2 \
        + [f"[{N},{TOP_K}]"] * 2 * sigmoid + [f"[{h_shape[0]},{h_shape[1]}]"] * within
    assert kept() == sorted(want)
    with _untagged():
        assert kept() == []


def test_the_tagged_gradient_is_the_untagged_gradient(expert_stack):
    """One result used twice in place of two equal results: under 'full' the
    tagged gradient is the untagged one bit for bit, and the 'none' gradient
    to the tolerance `test_remat_per_block_changes_nothing` allows."""
    loss, _, params, x, _ = expert_stack

    def grads(policy):
        return jax.tree_util.tree_leaves(
            jax.jit(jax.grad(loss(policy), argnums=(0, 1)))(params, x))

    full, none = grads("full"), grads("none")
    with _untagged():
        plain = grads("full")
    assert sum(float(jnp.abs(a).max()) > 0 for a in full) >= len(full) - 2   # `select_bias`: none
    for a, b, c in zip(full, plain, none):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-5 * float(jnp.abs(c).max()))


def test_the_experts_tags_lower_to_nothing_outside_a_checkpoint(expert_stack):
    """No `jax.checkpoint` around the block (remat 'none', every forward-only
    call): the step lowers to the untagged step's text."""
    loss, apply, params, x, _ = expert_stack

    def lowered():
        texts = (jax.jit(jax.grad(loss("none"))).lower(params, x).as_text(),
                 jax.jit(lambda p, h: apply(p, h)).lower(params[0], x).as_text())
        return [re.sub(r"@(\w+?)_\d+\b", r"@\1", t) for t in texts]

    tagged = lowered()
    assert "dl4j_remat_keep" not in "".join(tagged)
    with _untagged():
        assert lowered() == tagged
