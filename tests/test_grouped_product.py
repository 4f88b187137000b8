"""The experts' grouped product behind its one entry (`ops.linear.grouped_dot`
with its rule `grouped_width`): against a plain product group by group, on
both sides of the rule; and `RoutedExperts` at widths the rule pads against
the published-shape references, with gradients of the parameters' own
shapes. The rule's reason is a chip measurement (PERF.md section 6, PR 32);
what is proven here is that padding changes no number and no shape."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import common
from benchmark.reference import nemotron_h as nem
from benchmark.reference import qwen3_next as qwen
from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.layers import RoutedExperts
from deeplearning4j_tpu.ops import linear as ops

ROWS = 256
#: name -> (columns of x, rows of w, blocks, columns of a block of w)
WIDTHS = {
    "aligned": (64, 64, 1, 32),                   # both their own width: today's call
    "aligned_512": (512, 512, 1, 1024),
    "unaligned_expert_width": (64, 64, 1, 448),   # result padded 448 -> 512
    "unaligned_model_width": (512, 448, 1, 64),   # x came padded 448 -> 512
    "two_blocks": (64, 64, 2, 480),               # [gate | up], each 480 -> 512
}
GROUPS = {"even": (64, 64, 64, 64), "an_empty_group": (64, 0, 128, 64),
          "padding_in_the_last": (8, 8, 8, 232)}


def test_the_rule_on_both_sides():
    assert [ops.grouped_width(n) for n in (2688, 1856, 1920, 3712, 448)] == \
        [3072, 2048, 2048, 4096, 512]
    # already multiples of 512 (Qwen3-Next's, every power of two from 512 up)
    assert all(ops.grouped_width(n) == n for n in (512, 1024, 2048, 4096, 2560))
    # where a multiple of 512 would add more than a quarter the width stays
    assert all(ops.grouped_width(n) == n for n in (8, 16, 32, 128, 256, 384, 409, 1100))
    assert ops.grouped_width(410) == 512


def per_group(x, w, sizes, k, blocks, n):
    """The plain form: each group's rows times its own matrix, in float64."""
    x, w = np.asarray(x, np.float64)[:, :k], np.asarray(w, np.float64)
    out, start = np.zeros((x.shape[0], blocks * n)), 0
    for g, size in enumerate(sizes):
        out[start:start + size] = x[start:start + size] @ w[g]
        start += size
    return out


def unpadded(y, blocks, n):
    """The result's columns without each block's padding, and the padding."""
    y = np.asarray(y, np.float64).reshape(y.shape[0], blocks, -1)
    return y[..., :n].reshape(y.shape[0], -1), y[..., n:]


@pytest.mark.parametrize("mixed", [False, True], ids=["float32", "mixed_bf16"])
@pytest.mark.parametrize("groups", GROUPS)
@pytest.mark.parametrize("widths", WIDTHS)
def test_entry_is_the_product_group_by_group(widths, groups, mixed, rng):
    wide, k, blocks, n = WIDTHS[widths]
    sizes = GROUPS[groups]
    x = np.zeros((ROWS, wide), np.float32)
    x[:, :k] = rng.standard_normal((ROWS, k))
    w = (rng.standard_normal((len(sizes), k, blocks * n)) / np.sqrt(k)).astype(np.float32)
    co = rng.standard_normal((ROWS, blocks * n)).astype(np.float32)   # the result's cotangent
    if mixed:       # what the chip multiplies: operands rounded once, sums in float32
        x, w, co = (np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))
                    for a in (x, w, co))
    n_pad = ops.grouped_width(n)

    def f(x_, w_):
        y = ops.grouped_dot(x_, w_, jnp.asarray(sizes, jnp.int32), blocks)
        assert y.shape == (ROWS, blocks * n_pad)
        cut = y.reshape(ROWS, blocks, n_pad)[..., :n].reshape(ROWS, blocks * n)
        return jnp.sum(cut.astype(jnp.float32) * co), y

    dtypes.set_mixed_precision(mixed)
    try:
        (_, y), (dx, dw) = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(
            jnp.asarray(x), jnp.asarray(w))
    finally:
        dtypes.set_mixed_precision(False)
    assert dx.shape == x.shape and dw.shape == w.shape      # the shapes that came
    tol = dict(rtol=0, atol=(2e-2 if mixed else 1e-5))      # bf16 results; float32 sums
    got, padding = unpadded(y, blocks, n)
    np.testing.assert_allclose(got, per_group(x, w, sizes, k, blocks, n), **tol)
    assert not padding.any()                                # exact zeros, not small numbers
    # d rows = cotangent x matrix^T group by group; the columns beyond k get nothing
    want_dx = per_group(co, np.swapaxes(w, 1, 2), sizes, blocks * n, 1, k)
    np.testing.assert_allclose(np.asarray(dx, np.float64)[:, :k], want_dx,
                               rtol=0, atol=tol["atol"] * 4)
    assert not np.asarray(dx)[:, k:].any()
    start = 0
    for g, size in enumerate(sizes):                        # d matrix = rows^T x cotangent
        want = np.asarray(x, np.float64)[start:start + size, :k].T @ co[start:start + size]
        np.testing.assert_allclose(np.asarray(dw[g], np.float64), want,
                                   rtol=0, atol=tol["atol"] * 16)
        start += size


# --- the layer at widths the rule pads, against the published-shape references ---
F, E, S, TOKENS = 448, 480, 96, 64          # model 448 -> 512, expert 480 -> 512


def draw(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[-2]), jnp.float32)


def layer_case(act, rng):
    """(layer, its parameters, the reference of (parameters, x [n, F]))."""
    mm = common.matmul(None)
    x = jnp.asarray(rng.standard_normal((1, TOKENS, F)), jnp.float32)
    if act == "swiglu":
        layer = RoutedExperts(n_experts=8, top_k=3, expert_width=E, shared_width=S,
                              experts_held=(2, 4), capacity_factor=2.0)
        p = {"router": draw(rng, F, 8), "Wgu": draw(rng, 4, F, 2 * E), "Wd": draw(rng, 4, E, F),
             "shared_Wgu": draw(rng, F, 2 * S), "shared_Wd": draw(rng, S, F),
             "shared_gate": draw(rng, F, 1)}
        cfg = dict(num_experts=4, num_experts_published=8, experts_first=2,
                   num_experts_per_tok=3, norm_topk_prob=True)
        names = {"router": "router", "wgu": "Wgu", "wd": "Wd", "shared_wgu": "shared_Wgu",
                 "shared_wd": "shared_Wd", "shared_gate": "shared_gate"}
        return layer, p, x, lambda q, x_: qwen.moe(
            {r: q[g] for r, g in names.items()}, x_, cfg, mm)
    layer = RoutedExperts(n_experts=8, top_k=3, expert_width=E, shared_width=S,
                          experts_held=(2, 4), capacity_factor=2.0, scoring="sigmoid",
                          routed_scale=2.5, expert_act="relu2", shared_gated=False)
    p = {"router": draw(rng, F, 8), "Wu": draw(rng, 4, F, E), "Wd": draw(rng, 4, E, F),
         "shared_Wu": draw(rng, F, S), "shared_Wd": draw(rng, S, F),
         "select_bias": jnp.asarray(0.1 * rng.standard_normal(8), jnp.float32)}
    cfg = dict(num_experts=4, experts_first=2, num_experts_per_tok=3, norm_topk_prob=True,
               routed_scaling_factor=2.5)
    names = {"router": "router", "w1": "Wu", "w2": "Wd", "shared_w1": "shared_Wu",
             "shared_w2": "shared_Wd", "select_bias": "select_bias"}
    return layer, p, x, lambda q, x_: nem.moe({r: q[g] for r, g in names.items()}, x_, cfg, mm)


@pytest.mark.parametrize("act", ["relu2", "swiglu"])
def test_layer_at_unaligned_widths_is_the_published_shape_reference(act, rng):
    layer, p, x, plain = layer_case(act, rng)
    assert layer.init_params(jax.random.PRNGKey(0), it.recurrent(F, TOKENS)).keys() == p.keys()
    state = layer.init_state(it.recurrent(F, TOKENS))
    co = jnp.asarray(rng.standard_normal((TOKENS, F)), jnp.float32)

    def mine(q, x_):
        y, st = layer.apply(q, x_, state=state, train=True, rng=None)
        return jnp.sum(y[0] * co), (y[0], st)

    (_, (y, st)), (gp, gx) = jax.value_and_grad(mine, argnums=(0, 1), has_aux=True)(p, x)
    want = plain(p, x[0])
    gp_want, gx_want = jax.grad(lambda q, x_: jnp.sum(plain(q, x_) * co), argnums=(0, 1))(p, x[0])
    assert int(st["counters"]["dropped"]) == 0
    np.testing.assert_allclose(y, want, atol=2e-5 * float(jnp.abs(want).max()))
    np.testing.assert_allclose(gx[0], gx_want, atol=2e-5 * float(jnp.abs(gx_want).max()))
    for name, g in gp.items():
        assert g.shape == p[name].shape, name                 # never the padded shape
        if name != "select_bias":                             # a leaf no gradient reaches
            np.testing.assert_allclose(g, gp_want[name], err_msg=name,
                                       atol=2e-5 * float(jnp.abs(gp_want[name]).max()))


def grouped_calls(layer, f, tokens, mixed=True):
    """The operand shapes of every grouped product, and (from, to) of every
    `pad` that widens a last axis, in the jaxpr of the layer's forward and
    backward at model width f (nothing is computed)."""
    shapes = jax.eval_shape(lambda: layer.init_params(jax.random.PRNGKey(0), it.recurrent(f, tokens)))
    x = jax.ShapeDtypeStruct((1, tokens, f), jnp.bfloat16 if mixed else jnp.float32)
    state = layer.init_state(it.recurrent(f, tokens))

    def loss(q, x_):
        y, _ = layer.apply(q, x_, state=state, train=True, rng=None)
        return jnp.sum(y.astype(jnp.float32))

    dtypes.set_mixed_precision(mixed)
    try:
        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(shapes, x)
    finally:
        dtypes.set_mixed_precision(False)

    def walk(j):
        for e in j.eqns:
            yield e
            for inner in jax.core.jaxprs_in_params(e.params):
                yield from walk(inner)

    eqns = list(walk(jaxpr.jaxpr))
    calls = sorted((tuple(e.invars[0].aval.shape), tuple(e.invars[1].aval.shape))
                   for e in eqns if e.primitive.name == "ragged_dot_general")
    widened = sorted({(e.invars[0].aval.shape[-1], e.outvars[0].aval.shape[-1]) for e in eqns
                      if e.primitive.name == "pad"
                      and e.outvars[0].aval.shape[-1] > e.invars[0].aval.shape[-1]})
    grads = [tuple(v.aval.shape) for v in jaxpr.jaxpr.outvars]
    return calls, widened, grads, shapes


def test_the_form_each_configuration_takes():
    """Qwen3-Next's widths (2048, [gate | up] 2 x 512) are their own
    `grouped_width`: the layer emits the parent's program — `ragged_dot` on
    the operands as they come, nothing widened. Nemotron's (2688, 1856) run
    at 3072 and 2048, and the gradients keep the published shapes."""
    n = 128
    qwen_layer = RoutedExperts(n_experts=512, top_k=10, expert_width=512, shared_width=512,
                               experts_held=(0, 32), capacity_factor=16.0)
    cap = qwen_layer.capacity(n)
    calls, widened, grads, _ = grouped_calls(qwen_layer, 2048, n)
    assert widened == []
    forward = {((cap, 2048), (32, 2048, 1024)), ((cap, 512), (32, 512, 2048))}
    assert forward <= set(calls) and len(calls) == 6        # + two transposes each, of those widths
    assert {d for c in calls for d in c[0][1:] + c[1][1:]} == {512, 1024, 2048}

    nem_layer = RoutedExperts(n_experts=128, top_k=6, expert_width=1856, shared_width=3712,
                              experts_held=(0, 8), capacity_factor=16.0, scoring="sigmoid",
                              routed_scale=2.5, expert_act="relu2", shared_gated=False)
    cap = nem_layer.capacity(n)
    calls, widened, grads, shapes = grouped_calls(nem_layer, 2688, n)
    assert {((cap, 3072), (8, 3072, 2048)), ((cap, 2048), (8, 2048, 3072))} <= set(calls)
    assert {d for c in calls for d in c[0][1:] + c[1][1:]} == {2048, 3072}
    assert widened == [(1856, 2048), (2688, 3072)]          # the matrices; tokens and their cotangent
    flat = jax.tree_util.tree_leaves(shapes)
    assert grads[:len(flat)] == [tuple(s.shape) for s in flat]
    assert grads[-1] == (1, n, 2688)
