"""The double-gated short-convolution mixer, grouped-query attention with
plain-weight q/k norms and rotary positions over the whole head, and routed
experts with NO shared expert (the `lfm2_moe` shape), against the plain
reference (benchmark/reference/lfm2_moe.py) at a small size: the mixer's
output and every gradient at a length that is no multiple of anything, with
and without a key-padding mask; the zeros before the sequence; the attention
layer; the experts without `shared_*` leaves and with the published epsilon;
the 4 shares of an expert layer against the uncut layer; zoo -> config DSL ->
`ParallelWrapper.fit` against the reference's three Adam steps; what every new
argument's default leaves as it was; the mixer's scopes in the lowered step."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import program
from benchmark.reference import common
from benchmark.reference import lfm2_moe as ref
from benchmark.tests import tiny, tiny_ids, tiny_lfm2
from benchmark.traffic import train_stream as ts
from benchmark.traffic import train_stream_ids as tsi
from deeplearning4j_tpu import telemetry, zoo
from deeplearning4j_tpu.models import MultiLayerNetwork, serialization
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import (
    GatedAttention,
    GatedMLP,
    GatedShortConv,
    RnnOutput,
    RoutedExperts,
    SubLayerBlock,
    hybrid,
    ssm,
)
from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper
from deeplearning4j_tpu.parallel.mesh import build_mesh
from deeplearning4j_tpu.telemetry import trace as trace_mod

CFG = tiny_lfm2.lfm2()
ZOO_ARGS = {k: v for k, v in CFG["program"]["args"].items() if k != "remat"}
T = 77                      # no multiple of a chunk, a block or a tap count
IN = it.recurrent(32, T)
SEED = 2 ** 31 + 40
F32 = jnp.float32
MM = common.matmul(None)
NO_STATE = dict(state={}, train=True, rng=None)


@pytest.fixture(scope="module")
def weights():
    return ref.init_params(CFG, SEED)


def sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def conv_leaves(p):
    """The reference's conv-mixer leaves under the program's names."""
    return {"Win": p["win"], "conv": p["taps"], "Wout": p["wout"]}


def attn_leaves(p):
    return {"Wqkv": p["wqkv"], "q_norm": p["q_norm"], "k_norm": p["k_norm"], "Wo": p["wo"]}


def close(got, want, rtol=2e-4, scale=2e-5, err_msg=""):
    np.testing.assert_allclose(got, want, rtol=rtol, atol=scale * float(jnp.abs(want).max()),
                               err_msg=err_msg)


# ---------------------------------------------------------------------------
# the convolution and the mixer
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("width", [3, 4, 1])
def test_short_conv_is_the_explicit_sum(width, rng):
    """c_t = sum_s w[width - 1 - s] u_{t-s}, zeros before the sequence."""
    u = jnp.asarray(rng.standard_normal((2, 11, 5)), F32)
    w = jnp.asarray(rng.standard_normal((width, 5)), F32)
    want = np.zeros((2, 11, 5), np.float32)
    for t in range(11):
        for s in range(min(width, t + 1)):
            want[:, t] += np.asarray(w)[width - 1 - s] * np.asarray(u)[:, t - s]
    np.testing.assert_allclose(hybrid.short_conv(u, w), want, atol=1e-5)


def test_the_first_two_tokens_read_zeros_before_the_sequence(weights, rng):
    """Token 0 sees its own tap alone, token 1 two taps: what a window would
    hold before the sequence is zero, not a wrapped or repeated token."""
    p = sub(weights, "l0.conv.")
    layer = GatedShortConv(conv_width=3)
    x = jnp.asarray(rng.standard_normal((1, T, 32)), F32)
    y = layer.apply(conv_leaves(p), x, **NO_STATE)[0]
    b_, c_, z = jnp.split(x[0] @ p["win"], 3, axis=-1)
    u = b_ * z
    by_hand = jnp.stack([c_[0] * (p["taps"][2] * u[0]),
                         c_[1] * (p["taps"][2] * u[1] + p["taps"][1] * u[0]),
                         c_[2] * (p["taps"][2] * u[2] + p["taps"][1] * u[1]
                                  + p["taps"][0] * u[0])]) @ p["wout"]
    close(y[0, :3], by_hand, scale=1e-5)
    # causal: the tokens after t change nothing at t; the two before it do
    later = layer.apply(conv_leaves(p), x.at[:, 40:].set(0.0), **NO_STATE)[0]
    np.testing.assert_array_equal(later[:, :40], y[:, :40])
    earlier = layer.apply(conv_leaves(p), x.at[:, 37].set(0.0), **NO_STATE)[0]
    np.testing.assert_array_equal(earlier[:, :37], y[:, :37])
    assert all(float(jnp.abs(earlier[0, t] - y[0, t]).max()) > 1e-6 for t in (37, 38, 39))
    np.testing.assert_array_equal(earlier[:, 40:], y[:, 40:])


@pytest.mark.parametrize("masked", [False, True])
def test_mixer_matches_the_reference_forward_and_gradients(masked, weights, rng):
    """`GatedShortConv` against the reference's `short_conv` on the same
    weights: the output, the gradient of every leaf and of x. Under a
    key-padding mask (rows of 77 and 50 tokens) the tokens kept equal the
    reference on the shorter row and the padded ones give and get nothing."""
    p = sub(weights, "l0.conv.")
    layer = GatedShortConv(conv_width=CFG["conv_L_cache"])
    x = jnp.asarray(rng.standard_normal((2, T, 32)), F32)
    ct = jnp.asarray(rng.standard_normal((2, T, 32)), F32)
    lengths = (T, 50) if masked else (T, T)
    mask = jnp.asarray(np.arange(T)[None, :] < np.array(lengths)[:, None], F32)

    def prog(q, x_):
        y = layer.apply(conv_leaves(q), x_, state={}, train=True, rng=None,
                        mask=mask if masked else None)[0]
        return jnp.sum(y * ct), y

    def plain(q, x_):
        with jax.default_matmul_precision("highest"):
            rows = [jnp.pad(ref.short_conv(q, row[:n], CFG, MM), ((0, T - n), (0, 0)))
                    for row, n in zip(x_, lengths)]
        y = jnp.stack(rows)
        return jnp.sum(y * ct), y

    (got, y_got), g_got = jax.jit(jax.value_and_grad(prog, (0, 1), has_aux=True))(p, x)
    (want, y_want), g_want = jax.jit(jax.value_and_grad(plain, (0, 1), has_aux=True))(p, x)
    close(y_got, y_want)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name in p:
        close(g_got[0][name], g_want[0][name], err_msg=name)
    close(g_got[1], g_want[1])
    if masked:
        assert not np.asarray(y_got[1, 50:]).any() and not np.asarray(g_got[1][1, 50:]).any()


def test_reference_controls_change_the_mixer(weights, rng):
    x = jnp.asarray(rng.standard_normal((T, 32)), F32)
    blk = lambda i, op: jax.jit(lambda w, x_: ref.block(w, x_, CFG, i, op))(weights, x)  # noqa: E731
    for i, controls in ((0, ("drop_taps", "swap_bc", ref.CONTROL)),
                        (1, ("drop_rope", "drop_expert", ref.CONTROL)),
                        (2, ("drop_taps", "swap_bc", "drop_expert"))):
        sound = blk(i, None)
        for control in controls:
            assert float(jnp.abs(blk(i, control) - sound).max()) > 1e-4, (i, control)
    # and a fault of the other mixer leaves this layer alone
    np.testing.assert_array_equal(blk(0, "drop_rope"), blk(0, None))
    np.testing.assert_array_equal(blk(1, "swap_bc"), blk(1, None))


def test_the_seeded_weights_make_order_and_roles_matter(weights):
    """`init_params`: channel 0 of the hidden state is a constant that the q
    and k columns of the attention layer and the B and z columns of the conv
    mixers alone read; nothing writes it."""
    np.testing.assert_array_equal(weights["embed"][:, 0], ref.CHANNEL)
    for name, leaf in weights.items():
        if name.endswith(ref.READS) and not name.endswith(("conv.win", "attn.wqkv")):
            assert not np.asarray(leaf)[..., 0, :].any(), name
        if name.endswith(ref.WRITES):
            assert not np.asarray(leaf)[..., 0].any(), name
    win = np.asarray(weights["l0.conv.win"])[0].reshape(3, 32)
    assert win[0].any() and win[2].any() and not win[1].any()        # B and z read it, C not
    qkv = np.asarray(weights["l1.attn.wqkv"])[0]
    assert qkv[:4 * 8].any() and qkv[4 * 8:6 * 8].any() and not qkv[6 * 8:].any()   # q, k; not v
    # query head h holds its key head's vector turned back look_back(h) positions
    k0 = jnp.asarray(qkv[4 * 8:5 * 8])
    q1 = jnp.asarray(qkv[8:16])
    theta = float(CFG["rope_parameters"]["rope_theta"])
    turned = ref.rotate(k0[None], theta, jnp.full((1,), -float(ref.look_back(1)), F32))[0]
    np.testing.assert_allclose(q1, turned, atol=1e-5)


# ---------------------------------------------------------------------------
# attention with plain-weight q/k norms and rotary over the whole head
# ---------------------------------------------------------------------------
def attention_layer(**kw):
    return GatedAttention(n_heads=4, n_kv_heads=2, head_dim=8, rotary_fraction=1.0,
                          rope_theta=1e6, eps=CFG["norm_eps"], gated=False, qk_norm=True,
                          qk_norm_zero_centered=False, **kw)


def test_attention_matches_the_reference_forward_and_gradients(weights, rng):
    p = sub(weights, "l1.attn.")
    layer = attention_layer()
    x = jnp.asarray(rng.standard_normal((2, T, 32)), F32)
    ct = jnp.asarray(rng.standard_normal((2, T, 32)), F32)

    def prog(q, x_):
        return jnp.sum(layer.apply(attn_leaves(q), x_, **NO_STATE)[0] * ct)

    def plain(q, x_, rope=True):
        with jax.default_matmul_precision("highest"):
            y = jnp.stack([ref.attention(q, row, CFG, MM, rope) for row in x_])
        return jnp.sum(y * ct)

    got, g_got = jax.jit(jax.value_and_grad(prog, (0, 1)))(p, x)
    want, g_want = jax.jit(jax.value_and_grad(plain, (0, 1)))(p, x)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name in p:
        close(g_got[0][name], g_want[0][name], err_msg=name)
    close(g_got[1], g_want[1])
    assert abs(float(plain(p, x, rope=False)) - float(want)) > 1e-3 * abs(float(want))


def test_plain_and_zero_centred_norm_weights_start_at_the_same_layer(rng):
    """`qk_norm_zero_centered` (the default, Qwen3-Next's 1 + w from zero)
    and the plain weight from one are the same function at the start and
    differ by the parametrisation alone; the default's leaves and jaxpr are
    the parent's."""
    x = jnp.asarray(rng.standard_normal((2, 19, 32)), F32)
    kw = dict(n_heads=4, n_kv_heads=2, head_dim=8, rotary_fraction=0.25)
    centred, plain = GatedAttention(**kw), GatedAttention(qk_norm_zero_centered=False, **kw)
    assert centred.qk_norm_zero_centered is True
    pc = centred.init_params(jax.random.PRNGKey(3), IN)
    pp = plain.init_params(jax.random.PRNGKey(3), IN)
    assert not np.asarray(pc["q_norm"]).any() and (np.asarray(pp["q_norm"]) == 1.0).all()
    np.testing.assert_array_equal(pc["Wqkv"], pp["Wqkv"])
    np.testing.assert_allclose(centred.apply(pc, x, **NO_STATE)[0],
                               plain.apply(pp, x, **NO_STATE)[0], atol=1e-6)
    shifted = dict(pp, q_norm=pp["q_norm"] - 1.0, k_norm=pp["k_norm"] - 1.0)
    np.testing.assert_allclose(centred.apply(shifted, x, **NO_STATE)[0],
                               plain.apply(pp, x, **NO_STATE)[0], atol=1e-6)


def test_sublayer_attention_defaults_are_the_parents():
    """The attention a pattern model wraps is what it was: no gate, no q/k
    norm, no positions (the Nemotron step must not move); this model's says
    every argument it means itself, and a block wraps whatever it is given."""
    def wrapped(model, kind):
        return next(l.sub for l in model.conf().layers
                    if isinstance(l, SubLayerBlock) and isinstance(l.sub, kind))

    old = wrapped(zoo.PatternHybridLM(hybrid_override_pattern="M*E", hidden_size=32,
                                      num_attention_heads=4, num_key_value_heads=2, head_dim=8),
                  GatedAttention)
    assert (old.gated, old.qk_norm, old.rotary_fraction) == (False, False, 0.0)
    assert set(old.init_params(jax.random.PRNGKey(0), IN)) == {"Wqkv", "Wo"}
    new = wrapped(zoo.ShortConvMoELM(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
                                     norm_eps=1e-5, rope_parameters={"rope_theta": 1e6}),
                  GatedAttention)
    assert (new.qk_norm, new.qk_norm_zero_centered, new.rotary_fraction, new.rope_theta,
            new.eps) == (True, False, 1.0, 1e6, 1e-5)
    assert not hasattr(SubLayerBlock, "kind") and not hasattr(ssm, "KINDS")
    assert isinstance(SubLayerBlock(sub=GatedShortConv(conv_width=3)).sub, GatedShortConv)


# ---------------------------------------------------------------------------
# experts without a shared expert
# ---------------------------------------------------------------------------
def experts(rank=0, n=64, held=16, eps=1e-6, **kw):
    return RoutedExperts(n_experts=n, top_k=4, expert_width=8, shared_width=0,
                         experts_held=(held * rank, held), capacity_factor=n / held,
                         norm_topk=True, scoring="sigmoid", routed_scale=1.0,
                         expert_act="swiglu", norm_eps=eps, **kw)


def test_no_shared_width_builds_no_shared_leaf_and_adds_nothing(rng):
    layer = experts()
    p = layer.init_params(jax.random.PRNGKey(1), IN)
    assert set(p) == {"router", "Wgu", "Wd", "select_bias"}
    assert set(layer.regularizable(p)) <= set(p)
    x = jnp.asarray(rng.standard_normal((2, 40, 32)), F32)
    y, st = layer.apply(p, x, state=layer.init_state(IN), train=True, rng=None)
    top, idx = layer.route(p, x.reshape(-1, 32))
    out, _, dropped = layer.routed(p, x.reshape(-1, 32), top, idx)
    assert int(dropped) == 0 and int(st["counters"]["dropped"]) == 0
    np.testing.assert_array_equal(y.reshape(-1, 32), out.astype(y.dtype))   # the routed terms alone
    # a layer WITH a shared expert keeps its leaves (the four other expert cells)
    shared = RoutedExperts(n_experts=8, top_k=2, expert_width=8, shared_width=16)
    assert {"shared_Wgu", "shared_Wd", "shared_gate"} <= set(
        shared.init_params(jax.random.PRNGKey(1), IN))
    assert shared.norm_eps == 1e-20


def test_the_published_epsilon_is_in_the_renormalisation(rng):
    """Weights = s / (sum of the chosen s + eps). Where every score is
    sigmoid(-40) = 4e-18 the published 1e-6 swamps the sum and 1e-20 does
    not; at ordinary scores the layer's weights are the formula's and the
    reference's."""
    p = experts().init_params(jax.random.PRNGKey(2), IN)
    low = dict(p, router=jnp.zeros_like(p["router"]).at[0].set(-40.0))
    x = jnp.zeros((6, 32), F32).at[:, 0].set(1.0)
    assert float(experts(eps=1e-6).route(low, x)[0].sum(-1).max()) < 1e-10
    np.testing.assert_allclose(experts(eps=1e-20).route(low, x)[0].sum(-1), 1.0, rtol=1e-2)
    x = jnp.asarray(rng.standard_normal((6, 32)), F32)
    top, idx = experts(eps=1e-6).route(p, x)
    chosen = jnp.take_along_axis(jax.nn.sigmoid(x @ p["router"]), idx, -1)
    np.testing.assert_allclose(top, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    with jax.default_matmul_precision("highest"):
        w = ref.route({"router": p["router"], "select_bias": p["select_bias"]}, x,
                      dict(CFG, num_experts_per_tok=4), MM)
    np.testing.assert_allclose(jnp.take_along_axis(w, idx, -1), top, rtol=1e-5)


def test_four_shares_add_up_to_the_uncut_layer(rng):
    """The published recipe — sigmoid scores, top-4 of 64 by score + bias,
    renormalised over the 4 chosen (+ 1e-6) x 1, NO shared expert — with each
    of 4 ranks holding 16 experts (0..15, 16..31, 32..47, 48..63): the ranks'
    outputs add up to the uncut reference's layer; nothing is counted twice
    because nothing is computed on every rank alike."""
    draw = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), F32)  # noqa: E731
    p = {"router": draw(32, 64), "select_bias": draw(64) * 0.1, "wgu": draw(64, 32, 16),
         "wd": draw(64, 8, 32)}
    x = jnp.asarray(rng.standard_normal((2, 40, 32)), F32)
    cfg = dict(CFG, num_experts=64, num_experts_published=64, experts_first=0,
               num_experts_per_tok=4, moe_intermediate_size=8)
    assert cfg["routed_scaling_factor"] == 1 and cfg["norm_topk_prob"] is True
    xf = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(p, xf, cfg, MM)
        parts = [ref.moe(dict(p, wgu=p["wgu"][16 * r:16 * r + 16], wd=p["wd"][16 * r:16 * r + 16]),
                         xf, cfg, MM, held=(16 * r, 16)) for r in range(4)]
    total = jnp.zeros_like(whole)
    for rank in range(4):
        layer = experts(rank)
        held = slice(16 * rank, 16 * rank + 16)
        mine = {"router": p["router"], "select_bias": p["select_bias"], "Wgu": p["wgu"][held],
                "Wd": p["wd"][held]}
        y, st = layer.apply(mine, x, state=layer.init_state(IN), train=True, rng=None)
        assert int(st["counters"]["dropped"]) == 0
        assert layer.capacity(80) == 80 * 4               # every assignment has a row
        close(y.reshape(-1, 32), parts[rank], err_msg=f"rank {rank}")
        total = total + y.reshape(-1, 32)
    close(total, whole)
    assert float(jnp.abs(parts[0]).max()) > 0.05 * float(jnp.abs(whole).max())   # a share is no zero


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def test_sublayer_kinds_follow_the_published_layer_types():
    published = tiny.config("lfm2-24b-a2b-l5")
    types, dense = published["layer_types"], published["num_dense_layers"]
    assert len(types) == 40 and types.count("full_attention") == 10 and dense == 2
    whole = zoo.ShortConvMoELM(**dict(ZOO_ARGS, num_hidden_layers=40, layers_first=0))
    kinds = whole.sublayer_kinds()
    assert [m for m, _ in kinds] == ["attention" if t == "full_attention" else "shortconv"
                                     for t in types]
    assert [f for _, f in kinds] == ["dense"] * 2 + ["experts"] * 38
    assert [i for i, (m, _) in enumerate(kinds) if m == "attention"] == list(range(2, 40, 4))
    # the cut: published layers 1..5
    cut = zoo.ShortConvMoELM(**dict(ZOO_ARGS, num_hidden_layers=5, layers_first=1))
    assert cut.sublayer_kinds() == [("shortconv", "dense"), ("attention", "experts"),
                                    ("shortconv", "experts"), ("shortconv", "experts"),
                                    ("shortconv", "experts")]
    assert ref.kinds(dict(CFG, num_hidden_layers=5)) == [
        ("conv", "dense"), ("attention", "moe"), ("conv", "moe"), ("conv", "moe"), ("conv", "moe")]
    # with no list: the family's pattern, attention every fourth layer from the third
    assert zoo.ShortConvMoELM(num_hidden_layers=8).sublayer_kinds() == kinds[:8]
    with pytest.raises(ValueError, match="layer_types"):
        zoo.ShortConvMoELM(**dict(ZOO_ARGS, num_hidden_layers=41)).sublayer_kinds()
    with pytest.raises(ValueError, match="sliding"):
        zoo.ShortConvMoELM(layer_types=["conv", "sliding"], num_hidden_layers=2).sublayer_kinds()


def test_the_blocks_carry_the_published_recipe():
    model = zoo.ShortConvMoELM(**ZOO_ARGS)
    subs = [l.sub for l in model.conf().layers if isinstance(l, SubLayerBlock)]
    assert [type(s) for s in subs] == [GatedShortConv, GatedMLP, GatedAttention, RoutedExperts,
                                       GatedShortConv, RoutedExperts, GatedShortConv, RoutedExperts]
    e = next(s for s in subs if isinstance(s, RoutedExperts))
    assert (e.n_experts, e.top_k, e.held(), e.shared_width, e.scoring, e.norm_topk, e.norm_eps,
            e.routed_scale, e.expert_act) == (8, 3, (2, 4), 0, "sigmoid", True, 1e-6, 1.0, "swiglu")
    a = next(s for s in subs if isinstance(s, GatedAttention))
    assert (a.n_heads, a.n_kv_heads, a.head_dim, a.rotary_fraction, a.rope_theta, a.qk_norm,
            a.qk_norm_zero_centered, a.gated, a.eps) == (4, 2, 8, 1.0, 1e6, True, False, False, 1e-5)
    assert next(s for s in subs if isinstance(s, GatedShortConv)).conv_width == 3
    assert next(s for s in subs if isinstance(s, GatedMLP)).width == 64


def batches(n=3, rows=2):
    return tsi.make_batches(CFG, dict(tiny_ids.TRAIN_IDS, distinct_batches=n), rows, SEED)


def test_zoo_model_takes_the_references_three_adam_steps():
    """zoo -> config DSL -> `ParallelWrapper.fit` on integer labels against
    the plain reference: each loss, the first gradient as Adam got it, the
    parameters' change after three steps, every leaf; float32. `install`
    names every leaf of the program: none is a shared expert's."""
    data = batches()
    p0 = jax.device_get(ref.init_params(CFG, SEED))
    want = tsi.reference_numbers(ref, CFG, p0, {}, data, 3)
    net = program.build_net(CFG)
    program.install(net, ref, CFG, p0, {})
    paths = ref.program_paths(CFG)
    assert len(jax.tree_util.tree_leaves(net.params)) == len(paths) == len(ref.leaf_shapes(CFG))
    assert not any("shared" in "/".join(path) for path in paths.values())
    log = ts.StepLog()
    net.set_listeners(log)
    pw = ParallelWrapper(net, mesh=build_mesh(MeshSpec(data=1), jax.devices()[:1]))
    stream = ts.make_stream([program.dataset(x, y) for x, y, _ in data], 2)
    got = ts.program_numbers(net, pw, stream, log, ref, CFG, p0, 3)
    rows = common.compare_training(got, want, {"loss_gap": 2e-6, "grad_norm_gap": 2e-4,
                                               "grad_norm_gap_median": 2e-5,
                                               "delta_norm_gap": 2e-3}, ref.COMPARISONS)
    assert all(r[3] for r in rows), rows
    # the selection bias is a leaf Adam leaves where it is
    bias = [k for k in want["grad_norms"] if k.endswith("select_bias")]
    assert len(bias) == 3
    assert all(want["grad_norms"][k] == got["grad_norms"][k] == 0.0 for k in bias)
    assert all(want["delta_norms"][k] == got["delta_norms"][k] == 0.0 for k in bias)
    log_ = telemetry.fit_log()[-1]
    assert "kda" not in log_ and "ssm" not in log_ and len(log_["experts"]) == 3
    assert all(e["dropped_assignments"] == 0 and 0.0 < e["capacity_fill"] <= 1.0
               for e in log_["experts"])
    # each fault of the new mathematics is another model
    for control in ("drop_taps", "swap_bc", "drop_rope"):
        other = tsi.reference_numbers(ref, CFG, p0, {}, data, 1, control)
        rows = common.compare_training(other, want, {"loss_gap": 2e-6, "grad_norm_gap": 2e-4,
                                                     "grad_norm_gap_median": 2e-5,
                                                     "delta_norm_gap": 2e-3},
                                       common.WORST_LEAF[:2])
        assert not all(r[3] for r in rows), control


def test_lean_reference_steps_are_the_common_ones():
    """The reference's own `train_steps` (Adam a leaf at a time, the moments
    on the host between steps, float32 under `jax_enable_x64`) against
    `common.train_steps`."""
    cfg = tiny_lfm2.lfm2(seq_len=40)
    data = tsi.make_batches(cfg, tiny_ids.TRAIN_IDS, 2, SEED)
    p0 = jax.device_get(ref.init_params(cfg, SEED))
    lean = tsi.reference_numbers(ref, cfg, p0, {}, data, 3)
    seq = [(b[0], b[2]) for b in data]
    plain = common.train_steps(ref, cfg, jax.device_put(p0), {}, seq)
    np.testing.assert_allclose(lean["losses"], plain["losses"], rtol=1e-6)
    for key in ("grad_norms", "delta_norms"):
        for leaf, v in plain[key].items():
            assert lean[key][leaf] == pytest.approx(v, rel=1e-4, abs=1e-9), (key, leaf)


def test_new_layer_and_zoo_class_round_trip(tmp_path, rng):
    layer = GatedShortConv(conv_width=3)
    conf = zoo.ShortConvMoELM(**ZOO_ARGS).conf()
    again = MultiLayerConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    nested = [l["sub"] for l in json.loads(conf.to_json())["layers"] if l["type"] == "SubLayerBlock"]
    assert nested[0] == {"type": "GatedShortConv", "conv_width": 3}
    assert nested[3]["type"] == "RoutedExperts" and nested[3]["norm_eps"] == 1e-06
    small = NeuralNetConfiguration(seed=3).list([
        layer, RnnOutput(n_out=5, loss="mcxent", activation="softmax")]).set_input_type(IN)
    assert '"conv_width": 3' in small.to_json()
    back = MultiLayerConfiguration.from_json(small.to_json())
    assert isinstance(back.layers[0], GatedShortConv) and back.to_json() == small.to_json()
    net = MultiLayerNetwork(conf).init()
    ids = jnp.asarray(rng.integers(0, 48, (2, T)), jnp.int32)
    want = net.output(ids)
    path = str(tmp_path / "lfm2.zip")
    serialization.write_model(net, path)
    got = serialization.restore_multi_layer_network(path).output(ids)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_remat_per_block_changes_nothing(rng):
    ids = jnp.asarray(rng.integers(0, 48, (2, T)), jnp.int32)
    outs = []
    for remat in (None, "full"):
        net = zoo.ShortConvMoELM(**ZOO_ARGS, remat=remat).init()
        net.fit(program.dataset(np.asarray(ids), np.roll(np.asarray(ids), -1, 1)))
        outs.append(float(net.score_))
    assert outs[0] == pytest.approx(outs[1], rel=1e-6)


def test_the_conv_stack_alone_knows_the_order_of_its_last_three_tokens(rng):
    """One conv layer over a dense feed-forward: the last token's logits
    depend on the two tokens before it, in their order, and on no other."""
    net = zoo.ShortConvMoELM(**dict(ZOO_ARGS, num_hidden_layers=1, layers_first=0)).init()
    ids = rng.integers(0, 48, (1, T))
    last = lambda a: np.asarray(net.output(jnp.asarray(a, jnp.int32)))[0, -1]  # noqa: E731
    far = ids.copy()
    far[0, :T - 3] = rng.permutation(far[0, :T - 3])
    np.testing.assert_allclose(last(far), last(ids), atol=1e-6)
    near = ids.copy()
    near[0, [T - 3, T - 2]] = near[0, [T - 2, T - 3]]
    if near[0, T - 3] != near[0, T - 2]:
        assert float(np.abs(last(near) - last(ids)).max()) > 1e-5


# ---------------------------------------------------------------------------
# the scopes
# ---------------------------------------------------------------------------
def test_the_lowered_step_names_the_mixers_four_parts():
    assert {"proj", "gates", "conv", "out"} <= set(trace_mod.SCOPE_PARTS)
    net = zoo.ShortConvMoELM(**ZOO_ARGS, remat="full").init()
    ids = jnp.zeros((2, T), jnp.int32)
    args = (net.params, net.state, net.opt_state, jnp.int32(0), jax.random.PRNGKey(0),
            ids, ids, None, None)
    text = jax.jit(net._train_step_fn()).trace(*args).lower().as_text(debug_info=True)
    for layer in (1, 5, 7):                               # the three conv mixers' blocks
        for part in ("proj", "gates", "conv", "out"):
            assert f"dl4j.L{layer}.sublayerblock/dl4j.gatedshortconv/{part}" in text, (layer, part)
    for part in ("proj", "gates", "rope", "attend", "out"):   # the attention block's, the rotation its own
        assert f"dl4j.L3.sublayerblock/dl4j.gatedattention/{part}" in text, part
    assert "routedexperts/shared" not in text
    assert "transpose(jvp(" in text and "gatedshortconv/conv" in text.split("transpose(jvp(", 1)[1]
