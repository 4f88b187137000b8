"""The state-space mixer, the two expert recipes and the one-sub-layer block
(nn/layers/ssm.py, nn/layers/hybrid.py) against the benchmark's plain
reference (benchmark/reference/nemotron_h.py) at a small size: the chunked
recurrence against the token-by-token one, values and gradients of every
input; the gated group norm and the biased convolution against written-out
formulas; the sigmoid router and the relu^2 experts; the expert layer's
shares; the pattern string; the counters; and the zoo class through
`ParallelWrapper.fit` against the reference's three Adam steps."""
import json
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import program
from benchmark.reference import common
from benchmark.reference import nemotron_h as ref
from benchmark.tests import tiny, tiny_ids, tiny_nemotron
from benchmark.traffic import train_stream as ts
from benchmark.traffic import train_stream_ids as tsi
from deeplearning4j_tpu import telemetry, zoo
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.models import MultiLayerNetwork, serialization
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers import (
    GatedAttention,
    Mamba2Mixer,
    RMSNorm,
    RoutedExperts,
    SubLayerBlock,
)
from deeplearning4j_tpu.nn.layers import hybrid, ssm
from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper
from deeplearning4j_tpu.parallel.mesh import build_mesh
from deeplearning4j_tpu.zoo.models import pattern_kinds

CFG = tiny_nemotron.nemotron_h()
ZOO_ARGS = {k: v for k, v in CFG["program"]["args"].items() if k != "remat"}
T = 80              # not a multiple of the chunk of 32
IN = it.recurrent(32, T)
SEED = 2 ** 31 + 11


@pytest.fixture(scope="module")
def weights():
    return ref.init_params(CFG, SEED)


def sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def renamed(p, prefix):
    """A reference sub-layer's leaves under the program's names."""
    return {path[-1]: p[name[len(prefix):]] for name, path in ref._BLOCK_LEAF.items()
            if name.startswith(prefix)}


def back(q, prefix):
    return {name[len(prefix):]: q[path[-1]] for name, path in ref._BLOCK_LEAF.items()
            if name.startswith(prefix)}


def mixer():
    return Mamba2Mixer(n_heads=4, head_dim=8, n_groups=2, state_dim=16, chunk=32)


def experts(**kw):
    args = dict(n_experts=8, top_k=3, expert_width=16, shared_width=32, experts_held=(2, 4),
                capacity_factor=2.0, scoring="sigmoid", routed_scale=2.5,
                expert_act="relu2", shared_gated=False)
    return RoutedExperts(**dict(args, **kw))


def layer_case(kind, weights):
    """(program layer, its params, reference fn of (params, x [t, d]))."""
    mm = common.matmul(None)
    if kind == "mamba":
        return (mixer(), renamed(sub(weights, "l0.mamba."), "mamba."),
                lambda q, x: ref.mamba(back(q, "mamba."), x, CFG, mm))
    if kind == "attention":
        layer = GatedAttention(n_heads=4, n_kv_heads=2, head_dim=16, rotary_fraction=0.0,
                               gated=False, qk_norm=False)
        return (layer, renamed(sub(weights, "l7.attn."), "attn."),
                lambda q, x: ref.attention(back(q, "attn."), x, CFG, mm))
    if kind == "experts":
        return (experts(), renamed(sub(weights, "l1.moe."), "moe."),
                lambda q, x: ref.moe(back(q, "moe."), x, CFG, mm))
    if kind == "norm":
        return (RMSNorm(eps=1e-5, zero_centered=False), {"w": weights["final_norm"]},
                lambda q, x: ref.rms(x, q["w"], 1e-5))
    i = {"block_mamba": 2, "block_experts": 3, "block_attention": 7}[kind]
    prefix = {"block_mamba": "mamba.", "block_experts": "moe.", "block_attention": "attn."}[kind]
    wraps = {"block_mamba": Mamba2Mixer, "block_experts": RoutedExperts,
             "block_attention": GatedAttention}[kind]
    layer = next(l for l in zoo.PatternHybridLM(**ZOO_ARGS).conf().layers
                 if isinstance(l, SubLayerBlock) and isinstance(l.sub, wraps))
    p = sub(weights, f"l{i}.")

    def plain(q, x):
        flat = {f"l{i}.norm": q["norm"]["w"]}
        flat.update({f"l{i}.{prefix}{k}": v for k, v in back(q["sub"], prefix).items()})
        return ref.block(flat, x, CFG, i)

    return layer, {"norm": {"w": p["norm"]}, "sub": renamed(sub(p, prefix), prefix)}, plain


KINDS = ["norm", "mamba", "attention", "experts", "block_mamba", "block_experts",
         "block_attention"]


@pytest.mark.parametrize("kind", KINDS)
def test_layer_matches_the_reference_forward_and_gradients(kind, weights, rng):
    layer, params, ref_fn = layer_case(kind, weights)
    x = jnp.asarray(rng.standard_normal((2, T, 32)), jnp.float32)
    ct = jnp.asarray(rng.standard_normal((2, T, 32)), jnp.float32)
    state = layer.init_state(IN)

    def prog(p, x_):
        y, _ = layer.apply(p, x_, state=state, train=True, rng=None)
        return y

    def plain(p, x_):
        with jax.default_matmul_precision("highest"):
            return jnp.stack([ref_fn(p, row) for row in x_])

    def both(f):
        return jax.jit(lambda p, x_: (f(p, x_), jax.grad(
            lambda p_, x__: jnp.sum(f(p_, x__) * ct), (0, 1))(p, x_)))

    (got, g_got), (want, g_want) = both(prog)(params, x), both(plain)(params, x)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=2e-4)
    flat_got, _ = jax.tree_util.tree_flatten_with_path(g_got)
    for (path, a), b in zip(flat_got, jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(a, b, atol=3e-5 * float(jnp.abs(b).max()) + 1e-7,
                                   rtol=5e-4, err_msg=str(path))
    if "experts" in kind:        # the bias chooses: no gradient reaches it
        g = g_got[0]["sub"] if "block" in kind else g_got[0]
        assert not np.any(np.asarray(g["select_bias"]))


#: (t, decay, (groups, heads), masked): lengths that are whole chunks of 32,
#: that are not, and that are shorter than one; slow and fast decays; one
#: group a head and several heads a group; with and without a mask
SSD_CASES = [(t, decay, (2, 2), False) for decay in ("near_one", "fast")
             for t in (64, 128, 80, 20, 33)]
SSD_CASES += [(t, "near_one", (2, 6), masked) for t in (96, 80) for masked in (False, True)]


@pytest.mark.parametrize("t,decay,heads,masked", SSD_CASES)
def test_chunked_ssd_is_the_token_recurrence(t, decay, heads, masked, rng):
    """Values and the gradient of every input, against the token-by-token
    recurrence of the reference."""
    g, h = heads
    p, s, c = 8, 16, 32
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    x, b, cm = draw(2, t, h, p), draw(2, t, g, s), draw(2, t, g, s)
    lo, hi = (1e-3, 2e-2) if decay == "near_one" else (0.05, 0.4)
    dt = jnp.asarray(rng.uniform(lo, hi, (2, t, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 8.0, (h,)), jnp.float32)
    mask = jnp.asarray(rng.uniform(size=(2, t)) > 0.25, jnp.float32) if masked else None
    ct = draw(2, t, h, p)

    def chunked(x_, dt_, a_, b_, c_):
        if mask is not None:
            dt_ = dt_ * mask[..., None]
        y, _ = ssm.ssd_chunked(*(hybrid.to_chunks(m, c) for m in (x_, dt_)), a_,
                               *(hybrid.to_chunks(m, c) for m in (b_, c_)))
        return hybrid.from_chunks(y, t)

    def token(x_, dt_, a_, b_, c_):
        if mask is not None:
            dt_ = dt_ * mask[..., None]
        rep = lambda m: jnp.repeat(m, h // g, axis=2)  # noqa: E731
        with jax.default_matmul_precision("highest"):
            return jnp.stack([ref.ssm_recurrence(x_[r], dt_[r], a_, rep(b_)[r], rep(c_)[r])
                              for r in range(2)])

    def both(f):
        return jax.jit(lambda *args: (f(*args), jax.grad(
            lambda *args_: jnp.sum(f(*args_) * ct), (0, 1, 2, 3, 4))(*args)))

    (got, g_got), (want, g_want) = both(chunked)(x, dt, a, b, cm), both(token)(x, dt, a, b, cm)
    assert got.shape == (2, t, h, p)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()), rtol=2e-4)
    for name, u, v in zip("x dt a b c".split(), g_got, g_want):
        np.testing.assert_allclose(u, v, atol=5e-5 * float(jnp.abs(v).max()) + 1e-7,
                                   rtol=1e-3, err_msg=name)


def test_a_lost_carry_shows(rng, monkeypatch):
    """With decays near one the state a chunk starts from matters: a scan
    that hands on nothing is the reference's "drop_carry" control."""
    t, h, g, p, s, c = 96, 2, 2, 8, 16, 32
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    x, b, cm = draw(1, t, h, p), draw(1, t, g, s), draw(1, t, g, s)
    dt = jnp.asarray(rng.uniform(1e-3, 2e-2, (1, t, h)), jnp.float32)
    a = -jnp.ones((h,), jnp.float32)

    def chunked():
        y, _ = ssm.ssd_chunked(*(hybrid.to_chunks(m, c) for m in (x, dt)), a,
                               *(hybrid.to_chunks(m, c) for m in (b, cm)))
        return hybrid.from_chunks(y, t)[0]

    sound = chunked()
    step = ssm._ssd_step
    monkeypatch.setattr(ssm, "_ssd_step", lambda st, db: step(jnp.zeros_like(st), db))
    broken = chunked()
    with jax.default_matmul_precision("highest"):
        control = ref.ssm_recurrence(x[0], dt[0], a, b[0], cm[0], chunk=c)
        whole = ref.ssm_recurrence(x[0], dt[0], a, b[0], cm[0])
    scale = float(jnp.abs(whole).max())
    np.testing.assert_allclose(sound, whole, atol=2e-5 * scale)
    np.testing.assert_allclose(broken, control, atol=2e-5 * scale)
    assert float(jnp.abs(broken - sound)[c:].max()) > 0.05 * scale
    np.testing.assert_allclose(broken[:c], sound[:c], atol=2e-5 * scale)   # the first chunk has none


@pytest.mark.parametrize("t,dtype", [(80, "float32"), (64, "float32"), (20, "float32"),
                                     (80, "bfloat16")])
def test_biased_convolution_is_the_causal_convolution(t, dtype, rng):
    """`conv_silu` with a bias, chunk-major at a chunk of 32, against
    silu(sum_j w_j x_{i - (cw - 1) + j} + b) written out; the gradients of
    x, w and b too."""
    h, d, cw, c = 3, 8, 4, 32
    x = jnp.asarray(rng.standard_normal((2, t, h, d)), dtype)
    w = jnp.asarray(0.5 * rng.standard_normal((cw, h * d)), jnp.float32)
    b = jnp.asarray(0.5 * rng.standard_normal((h * d,)), jnp.float32)
    ct = jnp.asarray(rng.standard_normal((2, t, h, d)), jnp.float32)

    def chunked(x_, w_, b_):
        y = hybrid.conv_silu(hybrid.to_chunks(x_, c), w_.reshape(cw, h, 1, d),
                             b_.reshape(h, 1, d))
        return hybrid.from_chunks(y, t)

    def plain(x_, w_, b_):
        xf = x_.astype(jnp.float32).reshape(2, t, h * d)
        padded = jnp.pad(xf, ((0, 0), (cw - 1, 0), (0, 0)))
        pre = sum(padded[:, j:j + t] * w_[j] for j in range(cw)) + b_
        return jax.nn.silu(pre).reshape(2, t, h, d)

    def both(f):
        return jax.jit(lambda *a: (f(*a), jax.grad(
            lambda *a_: jnp.sum(f(*a_) * ct), (0, 1, 2))(*a)))

    (got, g_got), (want, g_want) = both(chunked)(x, w, b), both(plain)(x, w, b)
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got, want, atol=tol)
    for u, v in zip(g_got, g_want):
        assert u.dtype == v.dtype
        np.testing.assert_allclose(np.asarray(u, np.float32), np.asarray(v, np.float32),
                                   atol=tol * float(jnp.abs(v.astype(jnp.float32)).max()) + tol)
    # without a bias it is the function the delta rule uses, to the bit
    np.testing.assert_array_equal(
        hybrid.conv_silu(hybrid.to_chunks(x, c), w.reshape(cw, h, 1, d)),
        hybrid.conv_silu(hybrid.to_chunks(x, c), w.reshape(cw, h, 1, d),
                         jnp.zeros((h, 1, d), jnp.float32)))


def test_gate_comes_before_the_group_norm(weights, rng):
    """y = group_rms(y silu(z)) w, one mean over each group's heads x head
    width channels — written out from the layer's own projections."""
    layer = mixer()
    params = renamed(sub(weights, "l0.mamba."), "mamba.")
    x = jnp.asarray(rng.standard_normal((1, 40, 32)), jnp.float32)
    inner, g = 32, 2
    # Wout = I lays the normed y bare; a large skip lifts it far above eps
    bare = dict(params, Wout=jnp.eye(inner, 32), D=jnp.full((4,), 100.0))
    y, _ = layer.apply(bare, x, state=layer.init_state(IN), train=False, rng=None)
    # the same with the norm's weight at one and then scaled by hand
    ones = dict(bare, norm=jnp.ones((inner,), jnp.float32))
    y1, _ = layer.apply(ones, x, state=layer.init_state(IN), train=False, rng=None)
    np.testing.assert_allclose(y, y1 * params["norm"], rtol=1e-5, atol=1e-6)
    # EACH group of 16 channels has mean square 1 before the weight (one
    # mean over all 32 would leave the groups apart from 1)
    ms = jnp.mean(jnp.square(y1.reshape(1, 40, g, inner // g)), axis=-1)
    np.testing.assert_allclose(ms, 1.0, atol=2e-3)
    # the gate is inside the norm: y silu(z) is normed, written out by the reference
    with jax.default_matmul_precision("highest"):
        want = ref.mamba(back(ones, "mamba."), x[0], CFG, common.matmul(None))
    np.testing.assert_allclose(y1[0], want, atol=2e-5 * float(jnp.abs(want).max()))


def test_the_bias_chooses_and_does_not_weigh(rng):
    layer = experts(experts_held=None)
    p = layer.init_params(jax.random.PRNGKey(0), IN)
    x = jnp.asarray(rng.standard_normal((50, 32)), jnp.float32)
    p["router"] = jnp.asarray(0.5 * rng.standard_normal((32, 8)), jnp.float32)
    scores = jax.nn.sigmoid(x @ p["router"])
    top, idx = layer.route(p, x)
    # no bias: the three largest scores, renormalised to sum 2.5
    want = np.sort(np.asarray(scores), axis=-1)[:, ::-1][:, :3]
    np.testing.assert_allclose(np.sort(np.asarray(top), -1)[:, ::-1],
                               2.5 * want / want.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(top.sum(-1), 2.5, rtol=1e-5)
    # a bias that lifts expert 7 above all makes every token choose it ...
    biased = dict(p, select_bias=jnp.zeros((8,)).at[7].set(10.0))
    top_b, idx_b = layer.route(biased, x)
    assert np.all(np.any(np.asarray(idx_b) == 7, axis=-1))
    # ... and its weight is its bare score's share, not the biased one's
    w7 = jnp.sum(jnp.where(idx_b == 7, top_b, 0.0), -1)
    chosen = jnp.take_along_axis(scores, idx_b, -1)
    np.testing.assert_allclose(w7, 2.5 * scores[:, 7] / chosen.sum(-1), rtol=1e-5)
    assert np.all(np.asarray(w7) < 2.5)
    # some choices change with a small bias, as the seeded one's do
    small = dict(p, select_bias=jnp.asarray(0.05 * rng.standard_normal(8), jnp.float32))
    assert np.any(np.sort(np.asarray(layer.route(small, x)[1]), -1)
                  != np.sort(np.asarray(idx), -1))


def test_relu2_experts_are_the_dense_loop(rng):
    """Routed and shared experts, sorted buffer and grouped product, against
    a loop over every expert and token."""
    layer = experts(experts_held=None, capacity_factor=8.0)
    p = {k: jnp.asarray(0.3 * rng.standard_normal(v.shape), jnp.float32)
         for k, v in layer.init_params(jax.random.PRNGKey(1), IN).items()}
    x = jnp.asarray(rng.standard_normal((1, 24, 32)), jnp.float32)
    y, st = layer.apply(p, x, state=layer.init_state(IN), train=True, rng=None)
    top, idx = layer.route(p, x[0])
    want = np.zeros((24, 32), np.float32)
    for n in range(24):
        for w, e in zip(np.asarray(top[n]), np.asarray(idx[n])):
            hdn = np.maximum(np.asarray(x[0, n]) @ np.asarray(p["Wu"][e]), 0.0) ** 2
            want[n] += w * (hdn @ np.asarray(p["Wd"][e]))
        hdn = np.maximum(np.asarray(x[0, n]) @ np.asarray(p["shared_Wu"]), 0.0) ** 2
        want[n] += hdn @ np.asarray(p["shared_Wd"])
    np.testing.assert_allclose(y[0], want, atol=3e-5 * np.abs(want).max())
    assert int(st["counters"]["dropped"]) == 0
    assert set(p) == {"router", "select_bias", "Wu", "Wd", "shared_Wu", "shared_Wd"}


def test_the_two_recipes_keep_their_own_leaves():
    gated = RoutedExperts(n_experts=8, top_k=2, expert_width=16, shared_width=16)
    assert set(gated.init_params(jax.random.PRNGKey(0), IN)) == {
        "router", "Wgu", "Wd", "shared_Wgu", "shared_Wd", "shared_gate"}
    with pytest.raises(ValueError):
        experts(expert_act="gelu").init_params(jax.random.PRNGKey(0), IN)
    with pytest.raises(ValueError):
        experts(scoring="top1").init_params(jax.random.PRNGKey(0), IN)


def test_sixteen_shares_add_up_to_the_uncut_layer(rng):
    """Each of 16 ranks holds 2 of 32 experts; what every rank computes
    alike (the shared expert) is counted once."""
    draw = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)  # noqa: E731
    p = {"router": draw(32, 32), "select_bias": draw(32) * 0.1, "Wu": draw(32, 32, 16),
         "Wd": draw(32, 16, 32), "shared_Wu": draw(32, 24), "shared_Wd": draw(24, 32)}
    x = jnp.asarray(rng.standard_normal((2, 40, 32)), jnp.float32)
    cfg = dict(CFG, num_experts=32, num_experts_published=32, experts_first=0,
               num_experts_per_tok=5)
    mm = common.matmul(None)
    xf = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(back(p, "moe."), xf, cfg, mm)
        shared = ref.relu2(xf, p["shared_Wu"], p["shared_Wd"], mm)
    total = shared
    for rank in range(16):
        layer = experts(n_experts=32, top_k=5, shared_width=24, experts_held=(2 * rank, 2),
                        capacity_factor=8.0)
        mine = dict(p, Wu=p["Wu"][2 * rank:2 * rank + 2], Wd=p["Wd"][2 * rank:2 * rank + 2])
        y, st = layer.apply(mine, x, state=layer.init_state(IN), train=True, rng=None)
        assert int(st["counters"]["dropped"]) == 0
        total = total + (y.reshape(-1, 32) - shared)
    np.testing.assert_allclose(total, whole, atol=2e-5 * float(jnp.abs(whole).max()))


def test_reference_controls_change_the_result(weights, rng):
    x = jnp.asarray(rng.standard_normal((130, 32)), jnp.float32)
    blk = lambda i, op: jax.jit(lambda w, x_: ref.block(w, x_, CFG, i, op))(weights, x)  # noqa: E731
    for i, controls in ((0, ("drop_carry", ref.CONTROL)),
                        (1, ("drop_expert", "drop_shared", "ignore_bias", ref.CONTROL)),
                        (7, (ref.CONTROL,))):
        sound = blk(i, None)
        for control in controls:
            assert float(jnp.abs(blk(i, control) - sound).max()) > 1e-4, (i, control)


def test_pattern_string_names_the_layers():
    assert pattern_kinds("MEMEMEM*E") == [
        "mamba", "experts", "mamba", "experts", "mamba", "experts", "mamba",
        "attention", "experts"]
    for bad in ("MEX", "", "M-E"):
        with pytest.raises(ValueError):
            pattern_kinds(bad)
    layers = zoo.PatternHybridLM(**ZOO_ARGS).conf().layers
    wraps = {"mamba": Mamba2Mixer, "attention": GatedAttention, "experts": RoutedExperts}
    assert [type(l.sub) for l in layers if isinstance(l, SubLayerBlock)] == [
        wraps[k] for k in pattern_kinds("MEMEMEM*E")]
    assert [ref.KINDS[ch] for ch in "M*E"] == ["mamba", "attn", "moe"]
    with pytest.raises(ValueError):
        zoo.PatternHybridLM(**dict(ZOO_ARGS, hybrid_override_pattern="MEQ")).conf()
    published = tiny.config("nemotron-3-nano-30b-a3b-l9")["published"]
    kinds = pattern_kinds(published["hybrid_override_pattern"])
    assert len(kinds) == published["num_hidden_layers"] == 52
    assert [kinds.count(k) for k in ("mamba", "experts", "attention")] == [23, 23, 6]
    assert published["hybrid_override_pattern"][35:44] == "MEMEMEM*E"


def batches(n=3, rows=2, t=T):
    return tsi.make_batches(dict(CFG, input={"kind": "tokens", "seq_len": t, "vocab": 48}),
                            dict(tiny_ids.TRAIN_IDS, distinct_batches=n), rows, SEED)


def test_zoo_model_takes_the_references_three_adam_steps():
    """zoo -> config DSL -> `ParallelWrapper.fit` on integer labels against
    the plain reference: each loss, the first gradient as Adam got it, the
    parameters' change after three steps, every leaf; float32."""
    cfg = CFG
    data = batches()
    p0 = jax.device_get(ref.init_params(cfg, SEED))
    want = tsi.reference_numbers(ref, cfg, p0, {}, data, 3)
    net = program.build_net(cfg)
    program.install(net, ref, cfg, p0, {})
    log = ts.StepLog()
    net.set_listeners(log)
    pw = ParallelWrapper(net, mesh=build_mesh(MeshSpec(data=1), jax.devices()[:1]))
    stream = ts.make_stream([program.dataset(x, y) for x, y, _ in data], 2)
    got = ts.program_numbers(net, pw, stream, log, ref, cfg, p0, 3)
    rows = common.compare_training(got, want, {"loss_gap": 2e-6, "grad_norm_gap": 2e-4,
                                               "grad_norm_gap_median": 2e-5,
                                               "delta_norm_gap": 2e-3}, ref.COMPARISONS)
    assert all(r[3] for r in rows), rows
    # the selection bias is a leaf Adam leaves where it is
    bias = [k for k in want["grad_norms"] if k.endswith("select_bias")]
    assert len(bias) == 4
    assert all(want["grad_norms"][k] == got["grad_norms"][k] == 0.0 for k in bias)
    assert all(want["delta_norms"][k] == got["delta_norms"][k] == 0.0 for k in bias)
    log_ = telemetry.fit_log()[-1]
    assert len(log_["ssm"]) == 4 and len(log_["experts"]) == 4


def test_lean_reference_steps_are_the_common_ones():
    cfg = tiny_nemotron.nemotron_h(seq_len=40)
    data = tsi.make_batches(cfg, tiny_ids.TRAIN_IDS, 2, SEED)
    p0 = jax.device_get(ref.init_params(cfg, SEED))
    lean = tsi.reference_numbers(ref, cfg, p0, {}, data, 3)
    seq = [(b[0], b[2]) for b in data]
    plain = common.train_steps(ref, cfg, jax.device_put(p0), {}, seq)
    np.testing.assert_allclose(lean["losses"], plain["losses"], rtol=1e-6)
    for key in ("grad_norms", "delta_norms"):
        for leaf, v in plain[key].items():
            assert lean[key][leaf] == pytest.approx(v, rel=1e-4, abs=1e-9), (key, leaf)


def test_ssm_counters_reach_the_fit_log_once_a_fit(rng):
    net = zoo.PatternHybridLM(**ZOO_ARGS).init()
    ids = rng.integers(0, 48, (2, T)).astype(np.int32)
    ds = DataSet(ids, np.roll(ids, -1, 1).astype(np.int32))
    net.fit(ListDataSetIterator(DataSet.merge([ds, ds, ds]), batch=2))
    net.fit(ds)
    first, second = telemetry.fit_log()[-2:]
    assert [e["layer"] for e in first["ssm"]] == [f"layer_{i}" for i in (1, 3, 5, 7)]
    assert [e["layer"] for e in first["experts"]] == [f"layer_{i}" for i in (2, 4, 6, 9)]
    for e3, e1 in zip(first["ssm"], second["ssm"]):
        assert e3["steps"] == 3 and e1["steps"] == 1
        for e in (e3, e1):
            assert 0.0 < e["decay_min"] <= e["decay_mean"] < 1.0
            assert e["state_abs_max"] > 0.0
    for e in first["experts"]:
        assert e["steps"] == 3 and e["dropped_assignments"] == 0
    assert int(np.asarray(net.state["layer_1"]["counters"]["steps"])) == 4
    assert "layer_8" not in net.state or not net.state["layer_8"]      # attention counts nothing


def test_zoo_class_serialises_and_round_trips(tmp_path, rng):
    conf = zoo.PatternHybridLM(**ZOO_ARGS).conf()
    text = conf.to_json()
    again = MultiLayerConfiguration.from_json(text)
    assert json.loads(again.to_json()) == json.loads(text)
    net = MultiLayerNetwork(conf).init()
    ids = rng.integers(0, 48, (2, T)).astype(np.int32)
    ds = DataSet(ids, np.roll(ids, -1, 1).astype(np.int32))
    net.fit(ds)
    path = str(tmp_path / "pattern.zip")
    serialization.write_model(net, path)
    back_ = serialization.restore_multi_layer_network(path)
    np.testing.assert_array_equal(back_.output(ids), net.output(ids))
    assert back_.score(ds) == net.score(ds)
    assert int(back_.state["layer_1"]["counters"]["steps"]) == 1
    back_.fit(ds)
    assert np.isfinite(back_.score_)


def test_remat_per_block_changes_nothing(rng):
    ids = rng.integers(0, 48, (2, T)).astype(np.int32)
    ds = DataSet(ids, np.roll(ids, -1, 1).astype(np.int32))
    scores = []
    for remat in (None, "full"):
        net = zoo.PatternHybridLM(**ZOO_ARGS, remat=remat).init()
        net.fit(ListDataSetIterator(DataSet.merge([ds, ds]), batch=2))
        scores.append(net.score(ds))
    np.testing.assert_allclose(scores[0], scores[1], rtol=1e-5)


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("kernels", [False, True])
def test_ssm_core_mapped_over_rows_is_the_whole_batch(kernels, masked, weights, rng, monkeypatch):
    """Past `CORE_BYTES` of float32 convolution input the rows run one
    group at a time, each a checkpoint: same numbers, same gradients, same
    counters. `kernels`: a mixer of the shape the rule's kernel pair takes
    (chunks of 128, a state of 128), the kernels requested the way a TPU
    requests them ('auto') and run interpreted — against the XLA form too."""
    from deeplearning4j_tpu.ops import kernel_call, ssd_kernels

    t, width = (160, 32 + 2 * 128) if kernels else (T, 96)
    if kernels:
        layer = Mamba2Mixer(n_heads=2, head_dim=16, n_groups=1, state_dim=128, chunk=128)
        params = layer.init_params(jax.random.PRNGKey(5), IN)
    else:
        layer, params = mixer(), renamed(sub(weights, "l0.mamba."), "mamba.")
    x = jnp.asarray(rng.standard_normal((4, t, 32)), jnp.float32)
    mask = jnp.asarray(rng.uniform(size=(4, t)) > 0.2, jnp.float32) if masked else None

    def run():
        def loss(p, x_):
            y, st = layer.apply(p, x_, state=layer.init_state(IN), train=True, rng=None,
                                mask=mask)
            return jnp.sum(y * y), (y, st)
        return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(params, x)

    if kernels:
        xla = run()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(kernel_call, "interpret", lambda: True)
    with mock.patch.object(ssd_kernels, "ssd_chunk_kernels", wraps=ssd_kernels.ssd_chunk_kernels) as ran:
        whole = run()
        monkeypatch.setattr(Mamba2Mixer, "CORE_BYTES", 2 * t * width * 4)   # 2 rows
        text = str(jax.make_jaxpr(lambda p, x_: layer.apply(
            p, x_, state=layer.init_state(IN), train=True, rng=None, mask=mask)[0])(params, x))
        mapped = run()
    assert ran.call_count == (3 if kernels else 0)
    heads = "2,16" if kernels else "4,8"
    assert f"f32[2,{t},{heads}]" in text and f"f32[4,{t},{heads}]" not in text
    for a, b in zip(jax.tree_util.tree_leaves(mapped), jax.tree_util.tree_leaves(whole)):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.abs(b).max()) + 1e-8)
    if kernels:
        for b, c in zip(jax.tree_util.tree_leaves(whole), jax.tree_util.tree_leaves(xla)):
            np.testing.assert_allclose(b, c, atol=1e-4 * float(jnp.abs(c).max()) + 1e-8)
