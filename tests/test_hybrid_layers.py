"""The hybrid decoder layers (nn/layers/hybrid.py) against the benchmark's
plain reference (benchmark/reference/qwen3_next.py) at a small size: every
layer forward and gradients on seeded weights, the chunked delta rule against
the token-by-token recurrence, the expert layer's shares, its static device
work, its overflow, its counters, and the zoo class."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as ref
from deeplearning4j_tpu import telemetry, zoo
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.models import ComputationGraph, MultiLayerNetwork, serialization
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn import updaters
from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import (
    GatedAttention,
    GatedDeltaNet,
    HybridBlock,
    RMSNorm,
    RnnOutput,
    RoutedExperts,
)
from deeplearning4j_tpu.nn.layers.base import Layer
from deeplearning4j_tpu.nn.layers import hybrid

CFG = dict(
    hidden_size=32, vocab_size=48, num_hidden_layers=4, full_attention_interval=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    partial_rotary_factor=0.25, rope_theta=1e7,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=8, linear_conv_kernel_dim=4,
    num_experts=4, num_experts_published=8, experts_first=2, num_experts_per_tok=3,
    moe_intermediate_size=16, shared_expert_intermediate_size=16,
    norm_topk_prob=True, rms_norm_eps=1e-6)
ZOO_ARGS = dict(
    vocab_size=48, hidden_size=32, num_hidden_layers=4, full_attention_interval=4,
    max_length=80, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=8, num_experts=4, num_experts_published=8,
    experts_first=2, num_experts_per_tok=3, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, capacity_factor=2.0)
T = 80              # not a multiple of the chunk of 64
IN = it.recurrent(32, T)


@pytest.fixture(scope="module")
def weights():
    return ref.init_params(CFG, 2 ** 31 + 5)


def sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def to_program(p, names):
    return {prog: p[r] for r, prog in names.items()}


ATTN = {"wqkv": "Wqkv", "qnorm": "q_norm", "knorm": "k_norm", "wo": "Wo"}
DELTA = {"wqkvz": "Wqkvz", "wba": "Wba", "conv": "conv", "a_log": "A_log",
         "dt_bias": "dt_bias", "norm": "norm", "wout": "Wout"}
MOE = {"router": "router", "wgu": "Wgu", "wd": "Wd", "shared_wgu": "shared_Wgu",
       "shared_wd": "shared_Wd", "shared_gate": "shared_gate"}


def layer_case(kind, weights):
    """(program layer, its params, reference fn of (params, x [t, d]))."""
    mm = ref.common.matmul(None)
    if kind == "attention":
        p = sub(weights, "l3.attn.")
        layer = GatedAttention(n_heads=4, n_kv_heads=2, head_dim=16)
        return layer, to_program(p, ATTN), lambda q, x: ref.attention(
            {r: q[g] for r, g in ATTN.items()}, x, CFG, mm)
    if kind == "delta":
        p = sub(weights, "l0.delta.")
        layer = GatedDeltaNet(n_key_heads=2, n_value_heads=4, key_dim=8, value_dim=8)
        return layer, to_program(p, DELTA), lambda q, x: ref.delta(
            {r: q[g] for r, g in DELTA.items()}, x, CFG, mm)
    if kind == "experts":
        p = sub(weights, "l1.moe.")
        layer = RoutedExperts(n_experts=8, top_k=3, expert_width=16, shared_width=16,
                              experts_held=(2, 4), capacity_factor=2.0)
        return layer, to_program(p, MOE), lambda q, x: ref.moe(
            {r: q[g] for r, g in MOE.items()}, x, CFG, mm)
    if kind == "norm":
        layer = RMSNorm()
        return layer, {"w": weights["final_norm"]}, lambda q, x: ref.rms(
            x, q["w"], CFG["rms_norm_eps"])
    i = {"block_delta": 1, "block_attention": 3}[kind]
    layer = HybridBlock(mixer=layer_case(kind.split("_")[1], weights)[0],
                        moe=layer_case("experts", weights)[0])
    leaves = {k: v for k, v in ref.program_paths(CFG).items()
              if k.startswith(f"l{i}.")}

    def nest(flat):
        out = {}
        for name, path in leaves.items():
            node = out
            for key in path[1:-1]:
                node = node.setdefault(key, {})
            node[path[-1]] = flat[name]
        return out

    def flatten(tree):
        out = {}
        for name, path in leaves.items():
            node = tree
            for key in path[1:]:
                node = node[key]
            out[name] = node
        return out

    return layer, nest(weights), lambda q, x: ref.block(flatten(q), x, CFG, i)


KINDS = ["norm", "attention", "delta", "experts", "block_delta", "block_attention"]


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
    for a, b in zip(jax.tree_util.tree_leaves(g_got), jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(a, b, atol=3e-5 * float(jnp.abs(b).max()) + 1e-7,
                                   rtol=5e-4)


#: (t, decay, (key heads, value heads), masked): the ten cases at equal head
#: counts, then fewer key than value heads — the path that repeats nothing —
#: with and without a mask, at a length that is and is not whole chunks
RULE_CASES = [(t, decay, (3, 3), False) for decay in ("near_one", "fast")
              for t in (64, 128, 80, 37, 130)]
RULE_CASES += [(t, decay, (2, 4), masked) for t in (128, 80)
               for decay in ("near_one", "fast") for masked in (False, True)]


@pytest.mark.parametrize("t,decay,heads,masked", RULE_CASES)
def test_chunked_delta_rule_is_the_token_recurrence(t, decay, heads, masked, rng, monkeypatch):
    b, (hk, hv), dk, dv = 2, heads, 8, 8
    q, k = (jnp.asarray(rng.standard_normal((b, t, hk, dk)), jnp.float32) for _ in "qk")
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jnp.asarray(rng.standard_normal((b, t, hv, dv)), jnp.float32)
    lo, hi = (1e-4, 1e-2) if decay == "near_one" else (0.05, 1.0)
    g = -jnp.asarray(rng.uniform(lo, hi, (b, t, hv)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 1.0, (b, t, hv)), jnp.float32)
    keep = rng.uniform(size=(b, t)) > 0.3 if masked else np.ones((b, t), bool)
    m = jnp.asarray(keep, jnp.float32)[..., None]

    def chunked(q, k, v, g, beta):
        """The program's rule, from and to [b, t, h, ...]; a masked token
        writes nothing and keeps the state (as `GatedDeltaNet._core` has it)."""
        o = hybrid.chunk_gated_delta_rule(*(hybrid.to_chunks(a) for a in (
            q, k, v, g * m, beta * m)))
        return hybrid.from_chunks(o, t)

    def plain(q, k, v, g, beta):
        """The recurrence over the tokens kept, each value head with its key
        head's q and k; zero where a token is masked."""
        q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
        rows = []
        for i in range(b):
            at = np.flatnonzero(keep[i])
            o = ref.delta_recurrence(*(a[i][at] for a in (q, k, v, g, beta)))
            rows.append(jnp.zeros((t, hv, dv), jnp.float32).at[at].set(o))
        return jnp.stack(rows)

    got = jax.jit(chunked)(q, k, v, g, beta) * m[..., None]
    want = jax.jit(plain)(q, k, v, g, beta)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))
    if decay == "near_one":   # the state reaches the last chunk: a lost carry shows
        step = hybrid._chunk_step
        monkeypatch.setattr(hybrid, "_chunk_step",
                            lambda s, ab: step(jnp.zeros_like(s), ab))
        broken = chunked(q, k, v, g, beta) * m[..., None]
        monkeypatch.undo()
        gap = float(jnp.abs(broken - want).max() / jnp.abs(want).max())
        assert (gap > 0.05) == (t > 64)
    if t not in (80, 128):
        return
    ct = jnp.asarray(rng.standard_normal(want.shape), jnp.float32) * m[..., None]
    g_got = jax.jit(jax.grad(lambda *a: jnp.sum(chunked(*a) * ct),
                             (0, 1, 2, 3, 4)))(q, k, v, g, beta)
    g_want = jax.jit(jax.grad(lambda *a: jnp.sum(plain(*a) * ct),
                              (0, 1, 2, 3, 4)))(q, k, v, g, beta)
    for a, b_ in zip(g_got, g_want):
        np.testing.assert_allclose(a, b_, atol=1e-4 * float(jnp.abs(b_).max()))


@pytest.mark.parametrize("t", [128, 80, 200])       # two whole chunks; padded; four
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_chunked_convolution_is_the_causal_convolution(t, dtype, rng):
    """`conv_silu` on chunk-major rows against the plain padded convolution
    over the tokens, forward and its hand-written backward; a bf16 input's
    cotangent is the float32 one rounded once."""
    b, h, d, cw = 2, 3, 8, 4
    x = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32).astype(dtype)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (cw, h * d)), jnp.float32)
    ct = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)

    def chunked(x_, w_):
        y = hybrid.conv_silu(hybrid.to_chunks(x_), w_.reshape(cw, h, 1, d))
        return hybrid.from_chunks(y, t)

    def plain(x_, w_):
        padded = jnp.pad(x_.astype(jnp.float32).reshape(b, t, h * d),
                         ((0, 0), (cw - 1, 0), (0, 0)))
        return jax.nn.silu(sum(padded[:, j:j + t] * w_[j] for j in range(cw))).reshape(b, t, h, d)

    def both(f):
        return jax.jit(lambda x_, w_: (f(x_, w_), jax.grad(
            lambda *a: jnp.sum(f(*a) * ct), (0, 1))(x_, w_)))

    (got, (gx, gw)), (want, (rx, rw)) = both(chunked)(x, w), both(plain)(x.astype(jnp.float32), w)
    assert got.dtype == jnp.float32 and gx.dtype == x.dtype
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-5)
    np.testing.assert_allclose(gw, rw, atol=1e-5 * float(jnp.abs(rw).max()), rtol=1e-4)
    one_rounding = 2.0 ** -8 if dtype == "bfloat16" else 1e-5
    np.testing.assert_allclose(gx.astype(jnp.float32), rx, rtol=one_rounding,
                               atol=1e-6 * float(jnp.abs(rx).max()))


def test_reference_controls_change_the_result(weights, rng):
    x = jnp.asarray(rng.standard_normal((130, 32)), jnp.float32)
    blk = lambda op: jax.jit(lambda w, x_: ref.block(w, x_, CFG, 0, op))(weights, x)  # noqa: E731
    sound = blk(None)
    for control in ("drop_carry", "drop_expert", ref.CONTROL):
        other = blk(control)
        assert float(jnp.abs(other - sound).max()) > 1e-4, control


def full_layer_weights(rng, n_experts=32, d=32, f=16):
    draw = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)  # noqa: E731
    return {"router": draw(d, n_experts), "Wgu": draw(n_experts, d, 2 * f),
            "Wd": draw(n_experts, f, d), "shared_Wgu": draw(d, 2 * f),
            "shared_Wd": draw(f, d), "shared_gate": draw(d, 1)}


def test_sixteen_shares_add_up_to_the_uncut_layer(rng):
    """Each of 16 ranks holds 2 of 32 experts; what every rank computes
    alike (the shared expert) is counted once."""
    p = full_layer_weights(rng)
    x = jnp.asarray(rng.standard_normal((2, 40, 32)), jnp.float32)
    cfg = dict(CFG, num_experts=32, num_experts_published=32, experts_first=0,
               num_experts_per_tok=5)
    mm = ref.common.matmul(None)
    whole = ref.moe({r: p[g] for r, g in MOE.items()}, x.reshape(-1, 32), cfg, mm)
    shared = jax.nn.sigmoid(x.reshape(-1, 32) @ p["shared_gate"]) * ref.swiglu(
        x.reshape(-1, 32), p["shared_Wgu"], p["shared_Wd"], mm)
    total = shared
    for rank in range(16):
        layer = RoutedExperts(n_experts=32, top_k=5, expert_width=16, shared_width=16,
                              experts_held=(2 * rank, 2), capacity_factor=8.0)
        mine = dict(p, Wgu=p["Wgu"][2 * rank:2 * rank + 2], Wd=p["Wd"][2 * rank:2 * rank + 2])
        y, st = layer.apply(mine, x, state=layer.init_state(IN), train=True, rng=None)
        assert int(st["counters"]["dropped"]) == 0
        total = total + (y.reshape(-1, 32) - shared)
    np.testing.assert_allclose(total, whole, atol=2e-5 * float(jnp.abs(whole).max()))


def routing_case(rng, kind):
    """Weights that send every token to expert 0 first ("one"), or spread
    them ("even"), and the tokens."""
    p = full_layer_weights(rng, n_experts=8)
    x = jnp.asarray(np.abs(rng.standard_normal((1, 64, 32))), jnp.float32)
    if kind == "one":
        p["router"] = p["router"].at[:, 0].set(5.0).at[:, 1].set(4.0)
    p["Wgu"], p["Wd"] = p["Wgu"][:4], p["Wd"][:4]      # experts 0..3 are held
    return p, x


def test_expert_layer_work_is_a_function_of_shapes_alone(rng):
    layer = RoutedExperts(n_experts=8, top_k=2, expert_width=16, shared_width=16,
                          experts_held=(0, 4), capacity_factor=4.0)
    state = layer.init_state(IN)
    texts, loads = [], []
    for kind in ("one", "even"):
        p, x = routing_case(rng, kind)

        def step(p_, x_):
            def loss(p__):
                y, st = layer.apply(p__, x_, state=state, train=True, rng=None)
                return jnp.sum(y * y), st
            return jax.value_and_grad(loss, has_aux=True)(p_)

        texts.append(str(jax.make_jaxpr(step)(p, x)))
        (_, st), _ = step(p, x)
        loads.append(np.asarray(st["counters"]["load"]))
        want = ref.moe({r: p[g] for r, g in MOE.items()}, x[0],
                       dict(CFG, num_experts=4, num_experts_published=8,
                            experts_first=0, num_experts_per_tok=2),
                       ref.common.matmul(None))
        y, _ = layer.apply(p, x, state=state, train=False, rng=None)
        np.testing.assert_allclose(y[0], want, atol=2e-5 * float(jnp.abs(want).max()))
    assert texts[0] == texts[1]                  # same program, same shapes
    assert "while" not in texts[0] and "cond" not in texts[0]
    assert "ragged_dot" in texts[0]
    assert loads[0][0] == 64 and loads[0][1] == 64      # all to experts 0 and 1
    assert loads[1].max() < 64


def test_overflow_is_counted_and_left_out(rng):
    p, x = routing_case(rng, "one")            # 128 assignments to experts 0, 1
    layer = RoutedExperts(n_experts=8, top_k=2, expert_width=16, shared_width=16,
                          experts_held=(0, 4), capacity_factor=1.0)
    cap = layer.capacity(64)                   # 64 expected -> 128 rows at most
    small = RoutedExperts(n_experts=8, top_k=2, expert_width=16, shared_width=16,
                          experts_held=(0, 4), capacity_factor=0.6)
    assert cap == 128 and small.capacity(64) == 128  # rounds up to 128 rows
    tight = RoutedExperts(n_experts=8, top_k=2, expert_width=16, shared_width=16,
                          experts_held=(0, 4), capacity_factor=1.0)
    x2 = jnp.concatenate([x, x, x], axis=1)    # 192 tokens, 384 assignments, cap 256
    y, st = tight.apply(p, x2, state=tight.init_state(IN), train=True, rng=None)
    c = st["counters"]
    assert tight.capacity(192) == 256
    assert int(c["dropped"]) == 384 - 256 and int(c["capacity"]) == 256
    assert int(c["load"].sum()) == 384
    # sorted by expert: expert 0's 192 are kept, of expert 1's 192 the first
    # 64 tokens'; the rest is left out
    top, idx = tight.route(p, x2[0])
    keep = np.ones((192, 2), bool)
    second = np.asarray(idx) == 1
    keep[64:][second[64:]] = False
    cfg = dict(CFG, num_experts=4, num_experts_published=8, experts_first=0,
               num_experts_per_tok=2)
    mm = ref.common.matmul(None)
    q = {r: p[g] for r, g in MOE.items()}
    want = jax.nn.sigmoid(x2[0] @ p["shared_gate"]) * ref.swiglu(
        x2[0], p["shared_Wgu"], p["shared_Wd"], mm)
    for e in range(4):
        wt = jnp.sum(jnp.where((np.asarray(idx) == e) & keep, top, 0.0), axis=-1)
        want = want + wt[:, None] * ref.swiglu(x2[0], q["wgu"][e], q["wd"][e], mm)
    np.testing.assert_allclose(y[0], want, atol=2e-5 * float(jnp.abs(want).max()))


def test_counters_reach_the_fit_log_once_a_fit(rng):
    net = zoo.HybridMoELM(**ZOO_ARGS).init()
    ids = rng.integers(0, 48, (2, T)).astype(np.int32)
    ds = DataSet(ids, np.roll(ids, -1, 1).astype(np.int32))
    net.fit(ListDataSetIterator(DataSet.merge([ds, ds, ds]), batch=2))
    net.fit(ds)
    first, second = telemetry.fit_log()[-2:]
    assert [e["layer"] for e in first["experts"]] == [f"layer_{i}" for i in (1, 2, 3, 4)]
    for e3, e1 in zip(first["experts"], second["experts"]):
        assert e3["steps"] == 3 and e1["steps"] == 1
        assert e3["dropped_assignments"] == 0 and 0 < e3["capacity_fill"] <= 1
        assert e3["load_max_over_mean"] >= 1.0
        # 2 x 80 tokens x top-3, 4 of 8 experts held: about half arrive
        assert 0.2 * 480 < e1["assignments_per_step"] < 0.8 * 480
    total = int(np.asarray(net.state["layer_1"]["counters"]["steps"]))
    assert total == 4                        # cumulative on the device


@pytest.mark.parametrize("within", [True, False])
def test_the_fit_log_says_how_much_of_h_is_tagged(within, rng, monkeypatch):
    """`fit_log()['experts']` carries `h_kept_mb`: the MB of `h`, the first
    grouped product's output, that carry `REMAT_KEEP` a step (float32 here:
    no mixed precision), 0.0 beyond `hybrid.H_KEEP_BYTES`."""
    if not within:
        monkeypatch.setattr(hybrid, "H_KEEP_BYTES", 1024)
    net = zoo.HybridMoELM(**ZOO_ARGS).init()
    ids = rng.integers(0, 48, (2, T)).astype(np.int32)
    net.fit(DataSet(ids, np.roll(ids, -1, 1).astype(np.int32)))
    moe = RoutedExperts(n_experts=8, top_k=3, expert_width=16, experts_held=(2, 4),
                        capacity_factor=2.0)
    mb = moe.capacity(2 * T) * 2 * 16 * 4 / 1e6
    experts = telemetry.fit_log()[-1]["experts"]
    assert [e["h_kept_mb"] for e in experts] == [mb if within else 0.0] * 4


@dataclasses.dataclass
class RowCounter(Layer):
    """A second kind of counting layer, with no summary of its own."""

    def output_type(self, input_type):
        return input_type

    def has_params(self):
        return False

    def init_state(self, input_type):
        return {"counters": {"rows": jnp.zeros((), jnp.int32),
                             "per_feature": jnp.zeros((3,), jnp.float32)}}

    def apply(self, params, x, *, state, train, rng, mask=None):
        c = state["counters"]
        return x, {"counters": {"rows": c["rows"] + x.shape[0],
                                "per_feature": c["per_feature"] + 1.0}}


@pytest.mark.parametrize("model", ["mln", "graph"])
def test_any_layer_may_count_and_owns_its_summary(model, rng):
    """telemetry/counters.py knows no layer's schema: a layer without a
    `counter_summary` of its own reports its sums under `counters`, beside
    the expert layer's `experts`."""
    conf = NeuralNetConfiguration(seed=3, updater=updaters.Sgd(0.1))
    experts = RoutedExperts(n_experts=4, top_k=2, expert_width=8, shared_width=8,
                            capacity_factor=2.0)
    head = RnnOutput(n_out=5, loss="mcxent", activation="softmax")
    if model == "mln":
        net = MultiLayerNetwork(conf.list([RowCounter(), experts, head])
                                .set_input_type(it.recurrent(3, 6))).init()
        names = ("layer_0", "layer_1")
    else:
        net = ComputationGraph(
            conf.graph().add_inputs("in").add_layer("count", RowCounter(), "in")
            .add_layer("moe", experts, "count").add_layer("out", head, "moe")
            .set_outputs("out").set_input_types(it.recurrent(3, 6)).build()).init()
        names = ("count", "moe")
    x = rng.standard_normal((4, 6, 3)).astype(np.float32)
    y = rng.integers(0, 5, (4, 6)).astype(np.int32)
    net.fit(ListDataSetIterator(DataSet.merge([DataSet(x, y)] * 2), batch=4))
    entry = telemetry.fit_log()[-1]
    assert entry["counters"] == [{"layer": names[0], "rows": 8,
                                  "per_feature": [2.0, 2.0, 2.0]}]
    (e,) = entry["experts"]
    assert e["layer"] == names[1] and e["steps"] == 2 and e["dropped_assignments"] == 0


def test_zoo_class_serialises_and_round_trips(tmp_path, rng):
    model = zoo.HybridMoELM(**ZOO_ARGS)
    assert model.mixer_kinds() == ["delta", "delta", "delta", "attention"]
    conf = model.conf()
    text = conf.to_json()
    again = MultiLayerConfiguration.from_json(text)
    assert json.loads(again.to_json()) == json.loads(text)
    net = MultiLayerNetwork(conf).init()
    ids = rng.integers(0, 48, (2, T)).astype(np.int32)
    ds = DataSet(ids, np.roll(ids, -1, 1).astype(np.int32))
    net.fit(ds)
    path = str(tmp_path / "hybrid.zip")
    serialization.write_model(net, path)
    back = serialization.restore_multi_layer_network(path)
    np.testing.assert_array_equal(back.output(ids), net.output(ids))
    assert back.score(ds) == net.score(ds)
    assert int(back.state["layer_2"]["counters"]["steps"]) == 1
    back.fit(ds)                              # the restored optimizer state steps on
    assert np.isfinite(back.score_)


def test_remat_per_block_changes_nothing(rng):
    ids = rng.integers(0, 48, (2, T)).astype(np.int32)
    ds = DataSet(ids, np.roll(ids, -1, 1).astype(np.int32))
    scores = []
    for remat in (None, "full"):
        net = zoo.HybridMoELM(**ZOO_ARGS, remat=remat).init()
        net.fit(ListDataSetIterator(DataSet.merge([ds, ds]), batch=2))
        scores.append(net.score(ds))
    np.testing.assert_allclose(scores[0], scores[1], rtol=1e-5)


def test_delta_core_mapped_over_rows_is_the_whole_batch(weights, rng, monkeypatch):
    """Past `CORE_BYTES` of float32 convolution input the rows run one
    group at a time, each a checkpoint: same numbers, same gradients."""
    layer, params, _ = layer_case("delta", weights)
    x = jnp.asarray(rng.standard_normal((4, T, 32)), jnp.float32)
    mask = jnp.asarray(rng.uniform(size=(4, T)) > 0.2, jnp.float32)

    def run(m):
        def loss(p, x_):
            y, _ = layer.apply(p, x_, state={}, train=True, rng=None, mask=m)
            return jnp.sum(y * y), y
        return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(params, x)

    for m in (None, mask):
        whole = run(m)
        monkeypatch.setattr(GatedDeltaNet, "CORE_BYTES", 2 * T * 64 * 4)   # 2 rows
        text = str(jax.make_jaxpr(lambda p, x_: layer.apply(
            p, x_, state={}, train=True, rng=None, mask=m)[0])(params, x))
        mapped = run(m)
        monkeypatch.undo()
        assert f"f32[2,{T},4,8]" in text and f"f32[4,{T},4,8]" not in text
        for a, b in zip(jax.tree_util.tree_leaves(mapped), jax.tree_util.tree_leaves(whole)):
            np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.abs(b).max()) + 1e-8)


def eqns_in_order(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs (scan, checkpoint,
    pjit, custom calls) in place."""
    for eqn in jaxpr.eqns:
        subs = [v for v in eqn.params.values()
                if hasattr(v, "eqns") or hasattr(getattr(v, "jaxpr", None), "eqns")]
        for sub in subs:
            yield from eqns_in_order(getattr(sub, "jaxpr", sub))
        if not subs:
            yield eqn


@pytest.mark.parametrize("rows", [4, 2])        # the whole batch; mapped over rows
def test_delta_core_holds_one_layout(rows, rng, monkeypatch):
    """Between the projection and `Wout` nothing is repeated and nothing is
    re-tiled twice: q and k keep their `hk` heads until the first product
    (K K^T, Q K^T once a key head), and the chunked rule transposes no array
    at all — `to_chunks` in, `from_chunks` out. Key and value widths differ
    here so that a shape says whose array it is."""
    hk, hv, dk, dv, t = 2, 6, 16, 8, T          # T = 80 pads to 128
    layer = GatedDeltaNet(n_key_heads=hk, n_value_heads=hv, key_dim=dk, value_dim=dv)
    params = layer.init_params(jax.random.PRNGKey(3), IN)
    x = jnp.asarray(rng.standard_normal((4, t, 32)), jnp.float32)
    monkeypatch.setattr(GatedDeltaNet, "CORE_BYTES", rows * t * (2 * hk * dk + hv * dv) * 4)
    jaxpr = jax.make_jaxpr(lambda p, x_: layer.apply(
        p, x_, state={}, train=True, rng=None)[0])(params, x)
    eqns = list(eqns_in_order(jaxpr.jaxpr))
    batched = [i for i, e in enumerate(eqns) if e.primitive.name == "dot_general"
               and len(e.params["dimension_numbers"][1][0]) >= 3]
    first = eqns[batched[0]]
    assert [v.aval.shape[2] for v in first.invars] == [hk, hk]       # K K^T a key head
    repeated = rows * 128 * hv * dk                  # q or k at hv heads, padded time
    for e in eqns[:batched[0]]:
        for v in e.outvars:
            shape = getattr(v.aval, "shape", ())
            assert not (dk in shape[-2:] and np.prod(shape) == repeated), (e.primitive, shape)
    # an array is widened only from something small (a decay, a mask, a constant)
    for e in eqns:
        if e.primitive.name == "broadcast_in_dim":
            (src,), (out,) = e.invars, e.outvars
            grown = np.prod(out.aval.shape) > np.prod(getattr(src.aval, "shape", ()))
            assert not (grown and np.prod(src.aval.shape) >= rows * 128 * hk * dk), out.aval

    q = jnp.zeros((2, rows, hk, hybrid.CHUNK, dk), jnp.float32)
    v = jnp.zeros((2, rows, hv, hybrid.CHUNK, dv), jnp.float32)
    g = jnp.zeros((2, rows, hv, hybrid.CHUNK), jnp.float32)
    rule = jax.make_jaxpr(hybrid.chunk_gated_delta_rule)(q, q, v, g, g)
    names = {e.primitive.name for e in eqns_in_order(rule.jaxpr)}
    assert "transpose" not in names and "dot_general" in names
    # and the layer re-tiles each array once on the way in, once on the way out
    moved = [e.outvars[0].aval.shape for e in eqns if e.primitive.name == "transpose"
             and np.prod(e.outvars[0].aval.shape) >= 4 * t * hk * dk]   # not b | a, the mask
    assert len(moved) == 4, moved                          # q | k, v, z; the result



# ---------------------------------------------------------------------------
# the sandwich norm of a residual block (PR 44)
# ---------------------------------------------------------------------------
def _sandwich_case(rng, **block_args):
    from deeplearning4j_tpu.nn.layers import GatedMLP, SubLayerBlock

    block = SubLayerBlock(sub=GatedMLP(width=24, act="swiglu"), eps=1e-6, **block_args)
    params = block.init_params(jax.random.PRNGKey(0), IN)
    params = jax.tree.map(
        lambda a: a + 0.1 * jnp.asarray(rng.normal(size=a.shape), a.dtype), params)
    x = jnp.asarray(rng.normal(size=(2, T, 32)), jnp.float32)
    return block, params, x


def _plain_rms(u, w, eps=1e-6):
    return u / jnp.sqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps) * w


@pytest.mark.parametrize("masked", [False, True])
def test_sandwich_block_is_its_formula(masked, rng):
    """y = x + rms(sub(rms(x; w)); w_out), value and every gradient."""
    block, params, x = _sandwich_case(rng, post_norm=True)
    assert sorted(params) == ["norm", "norm_out", "sub"]
    mask = jnp.asarray(rng.integers(0, 2, (2, T)), jnp.float32) if masked else None

    def formula(p, a):
        inner, _ = block.sub.apply(p["sub"], _plain_rms(a, p["norm"]["w"]), state={},
                                   train=True, rng=None, mask=mask)
        return a + _plain_rms(inner, p["norm_out"]["w"])

    run = lambda p, a: block.apply(p, a, state={}, train=True, rng=None, mask=mask)[0]  # noqa: E731
    np.testing.assert_allclose(run(params, x), formula(params, x), rtol=2e-5, atol=2e-6)
    weigh = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    got = jax.grad(lambda p, a: jnp.sum(run(p, a) * weigh), argnums=(0, 1))(params, x)
    want = jax.grad(lambda p, a: jnp.sum(formula(p, a) * weigh), argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5 * float(jnp.abs(b).max() + 1e-9))
    # the second norm undoes the sub-layer's scale: what is added has rms ~ w_out
    added = run(params, x) - x
    if not masked:
        rms = jnp.sqrt(jnp.mean((added / params["norm_out"]["w"]) ** 2, axis=-1))
        np.testing.assert_allclose(rms, 1.0, rtol=1e-3)


def test_sandwich_norm_is_off_by_default_and_leaves_the_block_as_it_was(rng):
    block, params, x = _sandwich_case(rng)
    assert block.post_norm is False and sorted(params) == ["norm", "sub"]
    inner, _ = block.sub.apply(params["sub"], _plain_rms(x, params["norm"]["w"]), state={},
                               train=True, rng=None)
    got, _ = block.apply(params, x, state={}, train=True, rng=None)
    np.testing.assert_allclose(got, x + inner, rtol=2e-5, atol=2e-6)
    # an old configuration (no such key) reads back as the block it was
    d = block.to_json()
    d.pop("post_norm")
    assert Layer.from_json(d) == block
    # and the jaxpr of the default block holds no second norm (one rsqrt)
    text = str(jax.make_jaxpr(
        lambda p, a: block.apply(p, a, state={}, train=True, rng=None)[0])(params, x))
    assert text.count("rsqrt") == 1


# ---------------------------------------------------------------------------
# attention layers that are not alike (PR 47, the `laguna` shape): a gate a
# head, a window, a frequency schedule; `routed_scale` under both scorings
# ---------------------------------------------------------------------------
from benchmark.reference import common as ref_common  # noqa: E402
from benchmark.reference import laguna as laguna_ref  # noqa: E402
from benchmark.tests import tiny_laguna  # noqa: E402
from deeplearning4j_tpu.ops import rope_kernels  # noqa: E402

YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1, "beta_fast": 32,
        "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5}


def _attention_case(rng, **args):
    layer = GatedAttention(**{**dict(n_heads=4, n_kv_heads=2, head_dim=16, rotary_fraction=1.0,
                                     rope_theta=1e4, qk_norm=False, gated=True, gate="head"),
                              **args})
    params = layer.init_params(jax.random.PRNGKey(3), IN)
    x = jnp.asarray(rng.normal(size=(2, 40, 32)), jnp.float32)
    return layer, params, x


def _run(layer, params, x):
    return layer.apply(params, x, state=layer.init_state(IN), train=False, rng=None)[0]


def test_head_gate_is_its_formula(rng):
    """o_h sigmoid(x Wg)_h before Wo, Wg [f, n_heads] a leaf of its own beside a
    Wqkv that stays [q | k | v]."""
    layer, params, x = _attention_case(rng)
    assert {k: v.shape for k, v in params.items()} == {
        "Wqkv": (32, (4 + 2 * 2) * 16), "Wo": (64, 32), "Wg": (32, 4)}
    ungated = dataclasses.replace(layer, gated=False)
    bare = {k: v for k, v in params.items() if k != "Wg"}
    # the heads' outputs before Wo: the ungated layer with Wo the identity
    eye = dict(bare, Wo=jnp.eye(64, dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        o = ungated.apply(eye, x, state={}, train=False, rng=None)[0].reshape(2, 40, 4, 16)
        g = jax.nn.sigmoid(x @ params["Wg"])
        want = (o * g[..., None]).reshape(2, 40, 64) @ params["Wo"]
        got = _run(layer, params, x)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(got - _run(ungated, bare, x)).max()) > 0.01


def test_head_gate_is_the_element_gate_with_its_columns_repeated(rng):
    layer, params, x = _attention_case(rng)
    element = dataclasses.replace(layer, gate="element")
    q, k, v = jnp.split(params["Wqkv"], [64, 96], axis=-1)
    wide = {"Wqkv": jnp.concatenate([q, jnp.repeat(params["Wg"], 16, axis=1), k, v], axis=-1),
            "Wo": params["Wo"]}
    assert element.init_params(jax.random.PRNGKey(0), IN)["Wqkv"].shape == wide["Wqkv"].shape
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(_run(layer, params, x), _run(element, wide, x),
                                   rtol=2e-5, atol=2e-6)
    with pytest.raises(ValueError, match="gate='row'"):
        dataclasses.replace(layer, gate="row").init_params(jax.random.PRNGKey(0), IN)


def test_frequencies_are_the_closed_yarn_form():
    f, scale = hybrid.frequencies(64, 500000.0, YARN)
    e = 500000.0 ** (-2.0 * np.arange(32) / 64)
    c = lambda n: 64 * np.log(8192 / (2 * np.pi * n)) / (2 * np.log(500000.0))  # noqa: E731
    assert (int(np.floor(c(32))), int(np.ceil(c(1)))) == (9, 18)
    r = np.clip((np.arange(32) - 9) / 9, 0, 1)
    np.testing.assert_allclose(f, e * (1 - r) + e / 128 * r, rtol=1e-6)
    assert float(f[0]) == 1.0 and scale == 1.4852030263919618
    np.testing.assert_allclose(f[:10], e[:10], rtol=1e-6)          # the fast pairs as trained
    np.testing.assert_allclose(f[18:], e[18:] / 128, rtol=1e-6)    # the slow ones 128 x slower
    np.testing.assert_allclose(float(f[31]), e[31] / 128, rtol=1e-6)
    assert all(f[j] > f[j + 1] for j in range(31))
    # the published factor is the family's default for its factor
    assert hybrid.frequencies(64, 5e5, {k: v for k, v in YARN.items()
                                        if k != "attention_factor"})[1] == pytest.approx(
        0.1 * np.log(128) + 1)
    # the reference writes the same schedule out on its own
    fr, sr = laguna_ref.frequencies(64, YARN)
    np.testing.assert_allclose(f, fr, rtol=1e-6)
    assert sr == scale
    with pytest.raises(ValueError, match="rope_type='linear'"):
        hybrid.frequencies(64, 1e4, {"rope_type": "linear"})


def test_no_schedule_is_todays_rotation_and_tables(rng):
    """`rope_scaling=None`, and `rope_type` "default": bit-equal to the
    rotation and the kernel tables as they were (the jaxpr too)."""
    x = jnp.asarray(rng.normal(size=(1, 2, 24, 16)), jnp.float32)
    default = {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}
    want = hybrid.rotary(x, 8, 1e4)
    for scaling in (None, default):
        assert (np.asarray(hybrid.rotary(x, 8, 1e4, scaling=scaling)) == np.asarray(want)).all()
    assert str(jax.make_jaxpr(lambda a: hybrid.rotary(a, 8, 1e4, scaling=None))(x)) == str(
        jax.make_jaxpr(lambda a: hybrid.rotary(a, 8, 1e4))(x))
    # the angles the tables held before there was a schedule
    ang = jnp.arange(32, dtype=jnp.float32)[:, None] * 1e4 ** (
        -2.0 * jnp.arange(64, dtype=jnp.float32) / 128)
    cos, sin = rope_kernels.tables(32, 128, 128, 1e4)
    assert (np.asarray(cos[:, :64]) == np.asarray(jnp.cos(ang))).all()
    assert (np.asarray(sin[:, 64:]) == np.asarray(jnp.sin(ang))).all()
    for a, b in zip(rope_kernels.tables(32, 128, 128, 1e4, default), (cos, sin)):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_a_schedule_turns_and_scales_the_turned_part_alone(rng):
    """Half the head turns at the yarn frequencies, cos and sin times the
    factor; the other half passes through unscaled — in `rotary`, in the
    kernel tables and in the reference alike."""
    x = jnp.asarray(rng.normal(size=(1, 3, 40, 128)), jnp.float32)
    got = hybrid.rotary(x, 64, 5e5, scaling=YARN)
    want = jnp.moveaxis(laguna_ref.rotate(jnp.moveaxis(x[0], 1, 0), YARN), 0, 1)[None]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert (np.asarray(got[..., 64:]) == np.asarray(x[..., 64:])).all()
    norm = lambda a: jnp.sqrt(jnp.sum(a * a, axis=-1))  # noqa: E731
    np.testing.assert_allclose(norm(got[..., :64]), 1.4852030263919618 * norm(x[..., :64]),
                               rtol=1e-5)
    cos, sin = rope_kernels.tables(40, 128, 64, 5e5, tuple(sorted(YARN.items())))
    f, scale = hybrid.frequencies(64, 5e5, YARN)
    ang = jnp.arange(40, dtype=jnp.float32)[:, None] * f
    np.testing.assert_allclose(cos[:, :32], scale * jnp.cos(ang), rtol=1e-6)
    np.testing.assert_allclose(sin[:, 32:64], scale * jnp.sin(ang), rtol=1e-6, atol=1e-7)
    assert (np.asarray(cos[:, 64:]) == 1).all() and (np.asarray(sin[:, 64:]) == 0).all()


@pytest.mark.parametrize("kind", ["sliding", "full"])
def test_windowed_attention_layer_matches_the_reference(kind, rng):
    """`GatedAttention` as the zoo builds it for a layer of each type against
    the reference's layer, forward and every gradient (the XLA forms here;
    the kernels with a window are tests/test_window_attention.py's)."""
    cfg = tiny_laguna.laguna(seq_len=128)
    windowed = kind == "sliding"
    rec = laguna_ref.recipe(cfg, windowed)
    h = 3 if windowed else 2
    layer = GatedAttention(
        n_heads=h, n_kv_heads=1, head_dim=16, rotary_fraction=rec["partial_rotary_factor"],
        rope_theta=float(rec["rope_theta"]), qk_norm=False, gated=True, gate="head",
        rope_scaling=None if windowed else rec, window=8 if windowed else None)
    draw = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)  # noqa: E731
    p = {"wqkv": draw(32, (h + 2) * 16), "wg": draw(32, h), "wo": draw(h * 16, 32)}
    x = jnp.asarray(rng.standard_normal((2, 128, 32)), jnp.float32)
    weigh = jnp.asarray(rng.standard_normal((2, 128, 32)), jnp.float32)
    mm = ref_common.matmul(None)

    def reference(p, x):
        return jnp.stack([laguna_ref.attention(p, row, cfg, mm, windowed) for row in x])

    def program(p, x):
        mine = {"Wqkv": p["wqkv"], "Wg": p["wg"], "Wo": p["wo"]}
        return layer.apply(mine, x, state=layer.init_state(it.recurrent(32, 128)), train=True,
                           rng=None)[0]

    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(lambda p, x: jnp.sum(reference(p, x) * weigh),
                                          argnums=(0, 1))(p, x)
        got, got_g = jax.value_and_grad(lambda p, x: jnp.sum(program(p, x) * weigh),
                                        argnums=(0, 1))(p, x)
        np.testing.assert_allclose(program(p, x), reference(p, x), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5 * float(jnp.abs(b).max()))
    # every control of the reference is another layer
    for control in laguna_ref.CONTROLS:
        other = jnp.stack([laguna_ref.attention(p, row, cfg, mm, windowed, control) for row in x])
        moved = float(jnp.abs(other - reference(p, x)).max()) > 1e-3
        applies = {"drop_window": windowed, "window_511": windowed, "drop_yarn": not windowed,
                   "drop_rope_scale": not windowed, "drop_gate": True}[control]
        assert moved == applies, control


def test_a_windowed_layer_counts_its_plan(rng):
    layer, params, x = _attention_case(rng, window=8)
    state = layer.init_state(IN)
    assert sorted(state["counters"]) == ["band_keys", "steps", "visited_keys"]
    _, state = layer.apply(params, x, state=state, train=True, rng=None)
    _, state = layer.apply(params, x, state=state, train=True, rng=None)
    added = {k: np.atleast_1d(np.asarray(v)) for k, v in state["counters"].items()}
    key, entry = layer.counter_summary(added)
    band = (8 * 9 // 2 + 32 * 8) / 40
    assert key == "attention" and entry == {
        "steps": 2, "window": 8, "n_heads": 4, "n_kv_heads": 2,
        "band_keys_per_query": pytest.approx(band), "visited_keys_per_query": 40.0,
        "band_fill": pytest.approx(band / 40)}
    # no window: no state, nothing counted, the layer as it was
    plain_layer, params, x = _attention_case(rng)
    assert plain_layer.init_state(IN) == {}
    assert plain_layer.apply(params, x, state={}, train=True, rng=None)[1] == {}


def test_the_shares_add_up_to_the_uncut_layer(rng):
    """The cut's two kinds of share at a small size: the outputs of both head
    ranks of an attention layer (each with its rank's columns of Wq, Wk, Wv
    and Wg and its rows of Wo) add up to the uncut reference's layer, and so
    do the outputs of the four expert ranks with the shared expert — which
    every rank computes alike — counted once."""
    cfg = dict(tiny_laguna.laguna(seq_len=40), num_experts=8, num_experts_published=8,
               experts_first=0)
    draw = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)  # noqa: E731
    x = jnp.asarray(rng.standard_normal((2, 40, 32)), jnp.float32)
    xf = x.reshape(-1, 32)
    mm = ref_common.matmul(None)
    for windowed, h in ((True, 6), (False, 4)):
        kv, hd = 2, 16
        wq, wk, wv = draw(32, h * hd), draw(32, kv * hd), draw(32, kv * hd)
        wg, wo = draw(32, h), draw(h * hd, 32)
        whole = {"wqkv": jnp.concatenate([wq, wk, wv], axis=1), "wg": wg, "wo": wo}
        rec = laguna_ref.recipe(cfg, windowed)
        total = 0.0
        with jax.default_matmul_precision("highest"):
            want = jnp.stack([laguna_ref.attention(whole, row, cfg, mm, windowed) for row in x])
            for rank in range(2):
                q_ = slice(rank * h // 2 * hd, (rank + 1) * h // 2 * hd)
                k_ = slice(rank * hd, (rank + 1) * hd)
                mine = {"Wqkv": jnp.concatenate([wq[:, q_], wk[:, k_], wv[:, k_]], axis=1),
                        "Wg": wg[:, rank * h // 2:(rank + 1) * h // 2], "Wo": wo[q_]}
                layer = GatedAttention(
                    n_heads=h // 2, n_kv_heads=1, head_dim=hd,
                    rotary_fraction=rec["partial_rotary_factor"],
                    rope_theta=float(rec["rope_theta"]), qk_norm=False, gated=True, gate="head",
                    rope_scaling=None if windowed else rec, window=8 if windowed else None)
                y = layer.apply(mine, x, state=layer.init_state(IN), train=False, rng=None)[0]
                assert float(jnp.abs(y).max()) > 0.05 * float(jnp.abs(want).max())
                total = total + y
        np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5 * float(jnp.abs(want).max()))
    p = {"router": draw(32, 8), "wgu": draw(8, 32, 32), "wd": draw(8, 16, 32),
         "shared_wgu": draw(32, 32), "shared_wd": draw(16, 32)}
    total = 0.0
    with jax.default_matmul_precision("highest"):
        want = laguna_ref.moe(p, xf, cfg, mm)
        shared = laguna_ref.swiglu(xf, p["shared_wgu"], p["shared_wd"], mm)
        for rank in range(4):
            held = slice(2 * rank, 2 * rank + 2)
            layer = RoutedExperts(
                n_experts=8, top_k=3, expert_width=16, shared_width=16, experts_held=(2 * rank, 2),
                capacity_factor=4.0, norm_topk=True, scoring="softmax", routed_scale=2.5,
                shared_gated=False)
            mine = {"router": p["router"], "Wgu": p["wgu"][held], "Wd": p["wd"][held],
                    "shared_Wgu": p["shared_wgu"], "shared_Wd": p["shared_wd"]}
            y, st = layer.apply(mine, x, state=layer.init_state(IN), train=True, rng=None)
            assert int(st["counters"]["dropped"]) == 0
            part = laguna_ref.moe(dict(p, wgu=p["wgu"][held], wd=p["wd"][held]), xf, cfg, mm,
                                  held=(2 * rank, 2))
            np.testing.assert_allclose(y.reshape(-1, 32), part, rtol=2e-4, atol=1e-5)
            total = total + y.reshape(-1, 32) - shared
    np.testing.assert_allclose(total + shared, want, rtol=2e-4,
                               atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_routed_scale_holds_under_both_scorings(scoring, rng):
    layer = RoutedExperts(n_experts=8, top_k=3, expert_width=16, shared_width=0,
                          scoring=scoring, routed_scale=2.5, capacity_factor=4.0)
    params = layer.init_params(jax.random.PRNGKey(1), IN)
    xf = jnp.asarray(rng.normal(size=(24, 32)), jnp.float32)
    top, idx = layer.route(params, xf)
    one, idx1 = dataclasses.replace(layer, routed_scale=1.0).route(params, xf)
    assert (np.asarray(idx) == np.asarray(idx1)).all()
    np.testing.assert_allclose(top, 2.5 * one, rtol=1e-6)
    np.testing.assert_allclose(top.sum(-1), 2.5, rtol=1e-5)        # renormalised, then scaled
    # the default scale multiplies by one: the routes of every model that had none
    assert (np.asarray(dataclasses.replace(layer, routed_scale=1.0).route(params, xf)[0])
            == np.asarray(one)).all()
