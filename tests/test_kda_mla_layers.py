"""The per-channel-gated delta rule (KDA), latent attention (MLA), the flash
door at a key width that differs from the value width, and the zoo model
that stacks them on routed experts, against the plain reference
(benchmark/reference/kimi_linear.py) at a small size: the chunked rule
against the token-by-token recurrence (outputs and every input's gradient,
at decays the factorised rule overflows at), against the scalar rule where
all channels decay alike; each layer against the reference; the 32 shares of
an expert layer against the uncut layer; zoo -> config DSL ->
`ParallelWrapper.fit` against the reference's three Adam steps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import program
from benchmark.reference import common
from benchmark.reference import kimi_linear as ref
from benchmark.tests import tiny_ids, tiny_kimi
from benchmark.traffic import train_stream as ts
from benchmark.traffic import train_stream_ids as tsi
from deeplearning4j_tpu import telemetry, zoo
from deeplearning4j_tpu.models import MultiLayerNetwork, serialization
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers import (
    GatedMLP,
    KimiDeltaAttention,
    LatentAttention,
    RoutedExperts,
    SubLayerBlock,
)
from deeplearning4j_tpu.nn.layers import hybrid
from deeplearning4j_tpu.ops import attention as att
from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper
from deeplearning4j_tpu.parallel.mesh import build_mesh

CFG = tiny_kimi.kimi_linear()
ZOO_ARGS = {k: v for k, v in CFG["program"]["args"].items() if k != "remat"}
T = 80              # not a multiple of the chunk of 64
IN = it.recurrent(32, T)
SEED = 2 ** 31 + 33
F32 = jnp.float32


@pytest.fixture(scope="module")
def weights():
    return ref.init_params(CFG, SEED)


# ---------------------------------------------------------------------------
# the chunk rule
# ---------------------------------------------------------------------------
def draw(rng, b, t, h, dk, dv, strongest, equal_channels=False):
    """q, k (normalised), v, g (log decay, log-uniform down to -strongest a
    token; channel 0 a steady -strongest / 2), beta: [b, t, h, .] float32."""
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(rng.standard_normal((b, t, h, dk))) * dk ** -0.5
    k = unit(rng.standard_normal((b, t, h, dk)))
    v = rng.standard_normal((b, t, h, dv))
    g = -np.exp(rng.uniform(np.log(1e-3), np.log(strongest), (b, t, h, 1 if equal_channels else dk)))
    g = np.broadcast_to(g, (b, t, h, dk)).copy()
    if not equal_channels:
        g[..., 0] = -0.5 * strongest       # one channel that forgets fast, always
    beta = rng.uniform(0.0, 1.0, (b, t, h))
    return tuple(jnp.asarray(a, F32) for a in (q, k, v, g, beta))


def chunked(q, k, v, g, beta):
    t = q.shape[1]
    o, states = hybrid.chunk_channel_gated_delta_rule(
        *(hybrid.to_chunks(a) for a in (q, k, v, g, beta)))
    return hybrid.from_chunks(o, t), states


def token_by_token(q, k, v, g, beta):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([ref.delta_recurrence(*row) for row in zip(q, k, v, g, beta)])


#: (t, the strongest per-token log decay): whole chunks of 64, lengths that
#: are not, one shorter than a chunk; mild decays and decays whose running
#: sum over ONE chunk falls far below -100 (exp(+100) is not a float32)
KDA_CASES = [(128, 0.3), (64, 8.0), (150, 8.0), (70, 40.0), (40, 40.0), (192, 2.5)]


@pytest.mark.parametrize("t,strongest", KDA_CASES)
def test_chunked_kda_is_the_token_recurrence(t, strongest, rng):
    """Outputs and every input's gradient; finite and equal at any decay."""
    args = draw(rng, 2, t, 3, 16, 8, strongest)
    first = np.asarray(args[3])[:, :64].sum(axis=1).min()
    if strongest >= 8.0 and t >= 64:
        assert first < -100.0, first           # the factorised rule would overflow here
    ct = jnp.asarray(rng.standard_normal((2, t, 3, 8)), F32)

    def both(f):
        return jax.jit(lambda *a: (f(*a), jax.grad(
            lambda *a_: jnp.sum(f(*a_) * ct), tuple(range(5)))(*a)))

    (got, g_got), (want, g_want) = both(lambda *a: chunked(*a)[0])(*args), both(token_by_token)(*args)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, atol=3e-5 * float(jnp.abs(want).max()), rtol=2e-4)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), g_got, g_want):
        assert np.all(np.isfinite(a)), name
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.abs(b).max()) + 1e-7,
                                   rtol=1e-3, err_msg=name)


def test_the_factorised_rule_would_overflow_where_this_one_is_finite(rng):
    """(K exp(G)) (K exp(-G))^T: the form the scalar rule never needed."""
    q, k, v, g, beta = draw(rng, 1, 64, 2, 16, 8, 8.0)
    gc = jnp.cumsum(hybrid.to_chunks(g), axis=-2)
    kc = hybrid.to_chunks(k)
    naive = jnp.einsum("nbhid,nbhjd->nbhij", kc * jnp.exp(gc), kc * jnp.exp(-gc))
    assert not np.all(np.isfinite(naive))
    exact = hybrid._decayed_scores(kc, kc, gc)
    assert np.all(np.isfinite(exact))
    i = np.arange(64)
    want = np.einsum("id,jd,ijd->ij", np.asarray(kc[0, 0, 0], np.float64), np.asarray(kc[0, 0, 0], np.float64),
                     np.exp(np.minimum(np.asarray(gc[0, 0, 0], np.float64)[:, None]
                                       - np.asarray(gc[0, 0, 0], np.float64)[None, :], 0.0)))
    want = np.where(i[:, None] >= i[None, :], want, 0.0)
    np.testing.assert_allclose(exact[0, 0, 0], want, atol=2e-6)


@pytest.mark.parametrize("sub", [1, 4, 16, 64])
def test_decayed_scores_do_not_depend_on_the_sub_block(sub, rng, monkeypatch):
    q, k, v, g, beta = draw(rng, 1, 128, 2, 16, 8, 4.0)
    gc = jnp.cumsum(hybrid.to_chunks(g), axis=-2)
    want = hybrid._decayed_scores(hybrid.to_chunks(q), hybrid.to_chunks(k), gc)
    monkeypatch.setattr(hybrid, "SUB", sub)
    got = hybrid._decayed_scores(hybrid.to_chunks(q), hybrid.to_chunks(k), gc)
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("t", [128, 90])
def test_equal_channels_are_the_scalar_rule(t, rng):
    """A vector decay whose channels are all equal is `chunk_gated_delta_rule`."""
    q, k, v, g, beta = draw(rng, 2, t, 4, 16, 8, 0.5, equal_channels=True)
    got, _ = chunked(q, k, v, g, beta)
    c = [hybrid.to_chunks(a) for a in (q, k, v, g[..., 0], beta)]
    want = hybrid.from_chunks(hybrid.chunk_gated_delta_rule(*c), t)
    np.testing.assert_allclose(got, want, atol=3e-6 * float(jnp.abs(want).max()), rtol=1e-4)


def test_a_lost_carry_shows(rng, monkeypatch):
    """A scan over chunks that hands on nothing is the reference's
    "drop_carry" control (its chunk is the program's 64)."""
    args = draw(rng, 1, 150, 2, 16, 8, 0.05)
    sound, _ = chunked(*args)
    step = hybrid._chunk_step
    monkeypatch.setattr(hybrid, "_chunk_step", lambda s, ab: step(jnp.zeros_like(s), ab))
    lost, _ = chunked(*args)
    assert float(jnp.abs(lost - sound).max()) > 1e-2
    with jax.default_matmul_precision("highest"):
        want = ref.delta_recurrence(*(a[0] for a in args), chunk=64)
    np.testing.assert_allclose(lost[0], want, atol=3e-5 * float(jnp.abs(want).max()), rtol=2e-4)


# ---------------------------------------------------------------------------
# the layers against the reference
# ---------------------------------------------------------------------------
def sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def renamed(p, prefix):
    """A reference sub-layer's leaves under the program's names."""
    return {path[-1]: p[name[len(prefix):]] for name, path in ref._BLOCK_LEAF.items()
            if name.startswith(prefix)}


def back(q, prefix):
    return {name[len(prefix):]: q[path[-1]] for name, path in ref._BLOCK_LEAF.items()
            if name.startswith(prefix)}


def experts(**kw):
    args = dict(n_experts=8, top_k=3, expert_width=16, shared_width=16, experts_held=(2, 4),
                capacity_factor=2.0, scoring="sigmoid", routed_scale=2.446,
                expert_act="swiglu", shared_gated=False)
    return RoutedExperts(**dict(args, **kw))


def layer_case(kind, weights):
    """(program layer, its params, reference fn of (params, x [t, d]))."""
    mm = common.matmul(None)
    if kind == "kda":
        return (KimiDeltaAttention(n_heads=4, head_dim=8), renamed(sub(weights, "l0.kda."), "kda."),
                lambda q, x: ref.kda(back(q, "kda."), x, CFG, mm))
    if kind == "latent":
        layer = LatentAttention(n_heads=4, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8)
        return (layer, renamed(sub(weights, "l3.mla."), "mla."),
                lambda q, x: ref.mla(back(q, "mla."), x, CFG, mm))
    if kind == "dense":
        return (GatedMLP(width=64), renamed(sub(weights, "l0.mlp."), "mlp."),
                lambda q, x: ref.swiglu(x, q["Wgu"], q["Wd"], mm))
    if kind == "experts":
        return (experts(), renamed(sub(weights, "l1.moe."), "moe."),
                lambda q, x: ref.moe(back(q, "moe."), x, CFG, mm))
    # a whole layer: the mixer's block, then the feed-forward's
    i = {"layer_kda_dense": 0, "layer_kda_experts": 1, "layer_latent_experts": 3}[kind]
    blocks = [l for l in zoo.DeltaLatentMoELM(**ZOO_ARGS).conf().layers
              if isinstance(l, SubLayerBlock)][2 * i:2 * i + 2]
    p = sub(weights, f"l{i}.")
    # this layer's leaves: reference name -> (which block, .., the program's name)
    leaves = {name: path for name, path in ref._BLOCK_LEAF.items() if name in p}
    params = [{"norm": {"w": p[f"norm{j + 1}"]},
               "sub": {path[-1]: p[name] for name, path in leaves.items()
                       if path[0] == j and path[1] == "sub"}} for j in (0, 1)]

    class Pair:
        def init_state(self, input_type):
            return [b.init_state(input_type) for b in blocks]

        def apply(self, ps, x, *, state, train, rng):
            for b, q, s in zip(blocks, ps, state):
                x, _ = b.apply(q, x, state=s, train=train, rng=rng)
            return x, None

    def plain(ps, x):
        flat = {f"l{i}.{name}": ps[path[0]][path[1]][path[2]] for name, path in leaves.items()}
        return ref.block(flat, x, CFG, i)

    return Pair(), params, plain


KINDS = ["kda", "latent", "dense", "experts", "layer_kda_dense", "layer_kda_experts",
         "layer_latent_experts"]


@pytest.mark.parametrize("kind", KINDS)
def test_layer_matches_the_reference_forward_and_gradients(kind, weights, rng):
    layer, params, ref_fn = layer_case(kind, weights)
    x = jnp.asarray(rng.standard_normal((2, T, 32)), F32)
    ct = jnp.asarray(rng.standard_normal((2, T, 32)), F32)
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
        np.testing.assert_allclose(a, b, atol=5e-5 * float(jnp.abs(b).max()) + 1e-7,
                                   rtol=1e-3, err_msg=str(path))


def test_latent_attention_is_materialised_scores(weights, rng):
    """Written out here, in float32: one 4-wide key part shared by the four
    heads, keys 12 wide, values 8, scaled by 12^-0.5, no positions."""
    p = sub(weights, "l3.mla.")
    layer = LatentAttention(n_heads=4, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8)
    x = jnp.asarray(rng.standard_normal((1, 50, 32)), F32)
    with jax.default_matmul_precision("highest"):
        got, _ = layer.apply(renamed(p, "mla."), x, state={}, train=True, rng=None)
        q = (x[0] @ p["wq"]).reshape(50, 4, 12)
        ckr = x[0] @ p["wkva"]
        c = ckr[:, :16]
        c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True) + 1e-5) * p["kv_norm"]
        kv = (c @ p["wkvb"]).reshape(50, 4, 16)
        out = []
        for h in range(4):
            k = jnp.concatenate([kv[:, h, :8], ckr[:, 16:]], -1)
            s = (q[:, h] @ k.T) * 12 ** -0.5
            s = jnp.where(np.tril(np.ones((50, 50), bool)), s, -jnp.inf)
            out.append(jax.nn.softmax(s, -1) @ kv[:, h, 8:])
        want = jnp.concatenate(out, -1) @ p["wo"]
    np.testing.assert_allclose(got[0], want, atol=2e-5 * float(jnp.abs(want).max()), rtol=2e-4)
    # shuffling the tokens before the last one changes nothing for it but the order:
    # the layer knows no positions
    perm = np.concatenate([rng.permutation(49), [49]])
    shuffled, _ = layer.apply(renamed(p, "mla."), x[:, perm], state={}, train=True, rng=None)
    np.testing.assert_allclose(shuffled[0, -1], got[0, -1], atol=2e-6)


# ---------------------------------------------------------------------------
# the flash door at a key width that differs from the value width
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("t,dk,dv", [(256, 24, 16), (128, 192, 128), (1024, 12, 8)])
def test_flash_door_at_unequal_widths_is_sdpa(t, dk, dv, rng):
    """`attend(impl="pallas")`, the kernels interpreted, forward and vjp:
    one whole-sequence block, a head one program, and a block a program."""
    b, h = 1, 2
    q, k = (jnp.asarray(rng.standard_normal((b, h, t, dk)), F32) for _ in range(2))
    v = jnp.asarray(rng.standard_normal((b, h, t, dv)), F32)
    ct = jnp.asarray(rng.standard_normal((b, h, t, dv)), F32)
    assert att.choose_impl("pallas", b, t, (dk, dv), False) == "flash"

    def both(impl):
        f = lambda *a: att.attend(*a, causal=True, impl=impl)  # noqa: E731
        return jax.jit(lambda *a: (f(*a), jax.grad(lambda *a_: jnp.sum(f(*a_) * ct), (0, 1, 2))(*a)))

    with jax.default_matmul_precision("highest"):
        (got, g_got), (want, g_want) = both("pallas")(q, k, v), both("sdpa")(q, k, v)
    assert got.shape == (b, h, t, dv)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)
    for name, a, w in zip("qkv", g_got, g_want):
        assert a.shape == w.shape
        np.testing.assert_allclose(a, w, atol=5e-5 * float(jnp.abs(w).max()), rtol=1e-3, err_msg=name)


def test_blockwise_and_sdpa_take_unequal_widths(rng):
    q, k = (jnp.asarray(rng.standard_normal((1, 2, 96, 12)), F32) for _ in range(2))
    v = jnp.asarray(rng.standard_normal((1, 2, 96, 8)), F32)
    want = att.sdpa(q, k, v, causal=True)
    got = att.blockwise(q, k, v, causal=True, block_size=32)
    assert got.shape == (1, 2, 96, 8)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_kernel_names_carry_both_widths_only_where_they_differ():
    assert pk._flash_widths(256, 256) == {"d": 256}
    assert pk._flash_widths(192, 128) == {"d": 192, "dv": 128}
    assert pk.kernel_name("flash_fwd", jnp.bfloat16, bh=64, t=8192, **pk._flash_widths(192, 128),
                          bq=512, bk=512) == "dl4j_flash_fwd_bh64_t8192_d192_dv128_bq512_bk512_bfloat16"


DOOR = [  # (impl, t, (dk, dv) or d, on tpu) -> the implementation
    ("auto", 8192, (192, 128), True, "flash"), ("auto", 8192, 256, True, "flash"),
    ("auto", 8192, 64, True, "flash"), ("auto", 8192, (128, 128), True, "flash"),
    ("auto", 8192, (64, 128), True, "flash"), ("auto", 8192, (256, 64), True, "flash"),
    ("auto", 1024, (192, 72), True, "sdpa"), ("auto", 1024, 72, True, "sdpa"),
    ("auto", 1024, (200, 128), True, "sdpa"), ("auto", 256, (192, 128), True, "sdpa"),
    ("pallas", 256, (12, 8), False, "flash"), ("auto", 8192, (192, 128), False, "sdpa"),
    ("sdpa", 8192, (192, 128), True, "sdpa"), ("blockwise", 8192, (192, 72), True, "blockwise"),
]


@pytest.mark.parametrize("impl,t,d,tpu,want", DOOR)
def test_the_door_takes_the_pair_of_widths(impl, t, d, tpu, want, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu" if tpu else "cpu")
    monkeypatch.setenv("DL4J_TPU_PALLAS", "1")
    monkeypatch.setattr(att.kernel_call, "per_device_batch", lambda b: True)
    assert att.choose_impl(impl, 2, t, d, False) == want


def test_a_head_no_kernel_admits_raises_rather_than_materialise_17_gb(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("DL4J_TPU_PALLAS", "1")
    monkeypatch.setattr(att.kernel_call, "per_device_batch", lambda b: True)
    with pytest.raises(ValueError, match=r"\[2, 32, 8192, 192\].*72.*16\.0 GiB"):
        att.choose_impl("auto", 2, 8192, (192, 72), False, h=32)
    # the same head at a length whose scores fit goes to sdpa as before
    assert att.choose_impl("auto", 2, 1024, (192, 72), False, h=32) == "sdpa"
    # and a mask, which no kernel takes, is sdpa's whatever the widths
    assert att.choose_impl("auto", 2, 8192, (192, 128), True, h=32) == "sdpa"


# ---------------------------------------------------------------------------
# the share of the experts
# ---------------------------------------------------------------------------
def test_thirty_two_shares_add_up_to_the_uncut_layer(rng):
    """Each of 32 ranks holds 2 of 64 experts; what every rank computes
    alike (the shared expert) is counted once."""
    draw_ = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), F32)  # noqa: E731
    p = {"router": draw_(32, 64), "select_bias": draw_(64) * 0.1, "Wgu": draw_(64, 32, 16),
         "Wd": draw_(64, 8, 32), "shared_Wgu": draw_(32, 24), "shared_Wd": draw_(12, 32)}
    x = jnp.asarray(rng.standard_normal((2, 40, 32)), F32)
    cfg = dict(CFG, num_experts=64, num_experts_published=64, experts_first=0,
               num_experts_per_token=8)
    mm = common.matmul(None)
    xf = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(back(p, "moe."), xf, cfg, mm)
        shared = ref.swiglu(xf, p["shared_Wgu"], p["shared_Wd"], mm)
    total = shared
    for rank in range(32):
        layer = experts(n_experts=64, top_k=8, expert_width=8, shared_width=12,
                        experts_held=(2 * rank, 2), capacity_factor=32.0)
        mine = dict(p, Wgu=p["Wgu"][2 * rank:2 * rank + 2], Wd=p["Wd"][2 * rank:2 * rank + 2])
        y, st = layer.apply(mine, x, state=layer.init_state(IN), train=True, rng=None)
        assert int(st["counters"]["dropped"]) == 0
        total = total + (y.reshape(-1, 32) - shared)
    np.testing.assert_allclose(total, whole, atol=2e-5 * float(jnp.abs(whole).max()))


def test_reference_controls_change_the_result(weights, rng):
    x = jnp.asarray(rng.standard_normal((130, 32)), F32)
    blk = lambda i, op: jax.jit(lambda w, x_: ref.block(w, x_, CFG, i, op))(weights, x)  # noqa: E731
    for i, controls in ((0, ("drop_carry", "scalar_decay", ref.CONTROL)),
                        (1, ("drop_expert", "drop_shared", "ignore_bias", ref.CONTROL)),
                        (3, (ref.CONTROL,))):
        sound = blk(i, None)
        for control in controls:
            assert float(jnp.abs(blk(i, control) - sound).max()) > 1e-4, (i, control)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def test_the_mixer_list_and_the_dense_first_layer():
    model = zoo.DeltaLatentMoELM(**ZOO_ARGS)
    assert model.sublayer_kinds() == [("kda", "dense"), ("kda", "experts"), ("kda", "experts"),
                                      ("latent", "experts"), ("kda", "experts")]
    wraps = {"kda": KimiDeltaAttention, "latent": LatentAttention, "dense": GatedMLP,
             "experts": RoutedExperts}
    subs = [type(l.sub) for l in model.conf().layers if isinstance(l, SubLayerBlock)]
    assert subs == [wraps[k] for pair in model.sublayer_kinds() for k in pair]
    assert ref.kinds(CFG) == [(m.replace("latent", "mla"), f.replace("experts", "moe"))
                              for m, f in model.sublayer_kinds()]
    # the published lists, whole: 20 KDA layers and 7 latent ones of 27
    published = dict(ZOO_ARGS, num_hidden_layers=27)
    all_kinds = zoo.DeltaLatentMoELM(**published).sublayer_kinds()
    assert [m for m, _ in all_kinds].count("kda") == 20
    assert [i + 1 for i, (m, _) in enumerate(all_kinds) if m == "latent"] == \
        CFG["linear_attn_config"]["full_attn_layers"]
    assert [f for _, f in all_kinds].count("dense") == 1
    # a `sub` that is no layer is refused
    for no_layer in ("rwkv", None, 3):
        with pytest.raises(TypeError):
            SubLayerBlock(sub=no_layer)


def batches(n=3, rows=2, t=T):
    return tsi.make_batches(dict(CFG, input={"kind": "tokens", "seq_len": t, "vocab": 48}),
                            dict(tiny_ids.TRAIN_IDS, distinct_batches=n), rows, SEED)


def test_zoo_model_takes_the_references_three_adam_steps():
    """zoo -> config DSL -> `ParallelWrapper.fit` on integer labels against
    the plain reference: each loss, the first gradient as Adam got it, the
    parameters' change after three steps, every leaf; float32."""
    data = batches()
    p0 = jax.device_get(ref.init_params(CFG, SEED))
    want = tsi.reference_numbers(ref, CFG, p0, {}, data, 3)
    net = program.build_net(CFG)
    program.install(net, ref, CFG, p0, {})
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
    assert len(bias) == 4
    assert all(want["grad_norms"][k] == got["grad_norms"][k] == 0.0 for k in bias)
    assert all(want["delta_norms"][k] == got["delta_norms"][k] == 0.0 for k in bias)
    log_ = telemetry.fit_log()[-1]
    assert len(log_["kda"]) == 4 and len(log_["experts"]) == 4
    for entry in log_["kda"]:
        assert entry["steps"] >= 1
        assert 0.0 < entry["decay_min"] < entry["decay_mean"] < 1.0
        assert entry["state_abs_max"] > 0.0
    assert all(e["dropped_assignments"] == 0 for e in log_["experts"])


def test_lean_reference_steps_are_the_common_ones():
    """The reference's own `train_steps` (Adam a leaf at a time, float32
    under `jax_enable_x64`) against `common.train_steps`."""
    cfg = tiny_kimi.kimi_linear(seq_len=40)
    data = tsi.make_batches(cfg, tiny_ids.TRAIN_IDS, 2, SEED)
    p0 = jax.device_get(ref.init_params(cfg, SEED))
    lean = tsi.reference_numbers(ref, cfg, p0, {}, data, 3)
    seq = [(b[0], b[2]) for b in data]
    plain = common.train_steps(ref, cfg, jax.device_put(p0), {}, seq)
    np.testing.assert_allclose(lean["losses"], plain["losses"], rtol=1e-6)
    for key in ("grad_norms", "delta_norms"):
        for leaf, v in plain[key].items():
            assert lean[key][leaf] == pytest.approx(v, rel=1e-4, abs=1e-9), (key, leaf)


def test_zoo_class_serialises_and_round_trips(tmp_path, rng):
    conf = zoo.DeltaLatentMoELM(**ZOO_ARGS).conf()
    again = MultiLayerConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    net = MultiLayerNetwork(conf).init()
    ids = jnp.asarray(rng.integers(0, 48, (2, T)), jnp.int32)
    want = net.output(ids)
    path = str(tmp_path / "kimi.zip")
    serialization.write_model(net, path)
    got = serialization.restore_multi_layer_network(path).output(ids)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_remat_per_block_changes_nothing(rng):
    ids = jnp.asarray(rng.integers(0, 48, (2, T)), jnp.int32)
    outs = []
    for remat in (None, "full"):
        net = zoo.DeltaLatentMoELM(**ZOO_ARGS, remat=remat).init()
        net.fit(program.dataset(np.asarray(ids), np.roll(np.asarray(ids), -1, 1)))
        outs.append(float(net.score_))
    assert outs[0] == pytest.approx(outs[1], rel=1e-6)


@pytest.mark.parametrize("masked", [False, True])
def test_kda_core_mapped_over_rows_is_the_whole_batch(masked, weights, rng, monkeypatch):
    """Rows a group at a time (what the chip runs at 8192 tokens) against
    all rows at once; with a mask, the padded tokens write nothing."""
    layer = KimiDeltaAttention(n_heads=4, head_dim=8)
    p = renamed(sub(weights, "l0.kda."), "kda.")
    x = jnp.asarray(rng.standard_normal((4, T, 32)), F32)
    mask = None
    if masked:
        mask = jnp.asarray(np.arange(T)[None, :] < np.array([T, 50, 64, 7])[:, None], F32)
    run = lambda: layer.apply(p, x, state=layer.init_state(IN), train=True, rng=None, mask=mask)  # noqa: E731
    whole, st = run()
    monkeypatch.setattr(KimiDeltaAttention, "CORE_BYTES", 2 * T * 96 * 4)
    mapped, st2 = run()
    np.testing.assert_allclose(mapped, whole, atol=2e-6)
    for k_ in ("decay_sum", "decay_min_sum", "state_max_sum"):
        assert float(st2["counters"][k_]) == pytest.approx(float(st["counters"][k_]), rel=1e-5)
    if masked:   # a row's valid prefix is what the row alone would give
        alone, _ = layer.apply(p, x[1:2, :50], state=layer.init_state(IN), train=True, rng=None)
        np.testing.assert_allclose(whole[1, :50], alone[0], atol=2e-6)
        assert not np.any(np.asarray(whole[1, 50:]))
