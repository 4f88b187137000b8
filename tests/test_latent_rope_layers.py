"""Latent attention with decoupled rotary positions and the all-latent stack
over sigmoid-routed experts (the `deepseek_v3` shape), against the plain
reference (benchmark/reference/deepseek_v3.py) at a small size: the rotation
at both pairings and at an offset; the shared key part rotated once against
rotated a head; the layer, output and every gradient; without a theta the
layer the parent had, jaxpr and all; the 8 shares of an expert layer against
the uncut layer; zoo -> config DSL -> `ParallelWrapper.fit` against the
reference's three Adam steps; what a shuffled sequence changes; the `rope`
scope in the lowered step."""
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import program
from benchmark.reference import common
from benchmark.reference import deepseek_v3 as ref
from benchmark.tests import tiny_ids, tiny_kanana, tiny_kimi
from benchmark.traffic import train_stream as ts
from benchmark.traffic import train_stream_ids as tsi
from deeplearning4j_tpu import telemetry, zoo
from deeplearning4j_tpu.models import MultiLayerNetwork, serialization
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers import GatedMLP, LatentAttention, RoutedExperts, SubLayerBlock
from deeplearning4j_tpu.nn.layers import hybrid
from deeplearning4j_tpu.ops import attention as att
from deeplearning4j_tpu.ops import linear as ops
from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper
from deeplearning4j_tpu.parallel.mesh import build_mesh
from deeplearning4j_tpu.telemetry import trace as trace_mod

CFG = tiny_kanana.kanana()
ZOO_ARGS = {k: v for k, v in CFG["program"]["args"].items() if k != "remat"}
T = 80
IN = it.recurrent(32, T)
SEED = 2 ** 31 + 38
F32 = jnp.float32
THETA = 1e6


@pytest.fixture(scope="module")
def weights():
    return ref.init_params(CFG, SEED)


def sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def renamed(p):
    """The reference's latent leaves under the program's names."""
    return {"Wq": p["wq"], "Wkva": p["wkva"], "kv_norm": p["kv_norm"], "Wkvb": p["wkvb"],
            "Wo": p["wo"]}


def latent(**kw):
    return LatentAttention(n_heads=4, kv_rank=16, nope_dim=8, rope_dim=8, v_dim=8,
                           eps=CFG["rms_norm_eps"], **kw)


# ---------------------------------------------------------------------------
# the rotation
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("interleave", [True, False])
@pytest.mark.parametrize("start,width", [(0, 16), (7, 16), (0, 8), (5, 13)])
def test_rotation_is_the_references(interleave, start, width, rng):
    """`hybrid.rotary` on features [start, start + 8) of [b, h, t, width]
    against the reference's rotation of that part, token p at position p;
    what lies before and after passes through."""
    x = jnp.asarray(rng.standard_normal((2, 3, 37, width)), F32)
    got = hybrid.rotary(x, 8, THETA, start, interleave)
    part = jnp.moveaxis(x[..., start:start + 8], 2, 0)                 # [t, b, h, 8]
    want = jnp.moveaxis(ref.rotate(part, THETA, interleave), 0, 2)
    np.testing.assert_allclose(got[..., start:start + 8], want, atol=1e-6)
    np.testing.assert_array_equal(got[..., :start], x[..., :start])
    np.testing.assert_array_equal(got[..., start + 8:], x[..., start + 8:])
    assert float(jnp.abs(got[:, :, 1:] - x[:, :, 1:]).max()) > 0.1     # it turns
    np.testing.assert_allclose(got[:, :, 0], x[:, :, 0], atol=1e-7)    # position 0 does not


def test_the_two_pairings_differ_and_agree_after_a_relabelling(rng):
    """Interleaved pairs (2j, 2j + 1) are the half-split pairs (j, j + 4) of
    the de-interleaved features: the same rotation under a permutation."""
    x = jnp.asarray(rng.standard_normal((1, 2, 19, 8)), F32)
    inter = hybrid.rotary(x, 8, THETA, 0, True)
    half = hybrid.rotary(x, 8, THETA, 0, False)
    assert float(jnp.abs(inter - half).max()) > 0.1
    perm = np.array([0, 2, 4, 6, 1, 3, 5, 7])
    np.testing.assert_allclose(inter[..., perm], hybrid.rotary(x[..., perm], 8, THETA, 0, False),
                               atol=1e-6)


@pytest.mark.parametrize("interleave", [True, False])
def test_rotation_keeps_norms_and_depends_on_the_distance_alone(interleave, rng):
    q = jnp.asarray(rng.standard_normal((8,)), F32)
    k = jnp.asarray(rng.standard_normal((8,)), F32)
    rows = lambda a: jnp.broadcast_to(a, (1, 1, 50, 8))  # noqa: E731
    rq, rk = (hybrid.rotary(rows(a), 8, 100.0, 0, interleave)[0, 0] for a in (q, k))
    np.testing.assert_allclose(jnp.linalg.norm(rq, axis=-1), jnp.linalg.norm(q), rtol=1e-5)
    scores = rq @ rk.T
    for d in (0, 3, 17):
        diag = np.diagonal(np.asarray(scores), offset=-d)
        np.testing.assert_allclose(diag, diag[0], atol=2e-5)


@pytest.mark.parametrize("interleave", [True, False])
def test_the_shared_key_part_rotated_once_is_rotated_a_head(interleave, rng):
    kr = jnp.asarray(rng.standard_normal((2, 1, T, 8)), F32)
    once = jnp.broadcast_to(hybrid.rotary(kr, 8, THETA, 0, interleave), (2, 4, T, 8))
    a_head = hybrid.rotary(jnp.broadcast_to(kr, (2, 4, T, 8)), 8, THETA, 0, interleave)
    np.testing.assert_array_equal(once, a_head)


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("interleave", [True, False])
def test_layer_matches_the_reference_forward_and_gradients(interleave, weights, rng):
    """`LatentAttention` with a theta against the reference's `mla` on the
    same weights: the output and the gradient of every leaf and of x."""
    p = sub(weights, "l1.mla.")
    cfg = dict(CFG, rope_interleave=interleave)
    layer = latent(rope_theta=THETA, rope_interleave=interleave)
    x = jnp.asarray(rng.standard_normal((2, T, 32)), F32)
    ct = jnp.asarray(rng.standard_normal((2, T, 32)), F32)
    mm = common.matmul(None)

    def prog(q, x_):
        return jnp.sum(layer.apply(renamed(q), x_, state={}, train=True, rng=None)[0] * ct)

    def plain(q, x_):
        with jax.default_matmul_precision("highest"):
            y = jnp.stack([ref.mla(q, row, cfg, mm, ref.pairing(cfg)) for row in x_])
        return jnp.sum(y * ct)

    got, g_got = jax.jit(jax.value_and_grad(prog, (0, 1)))(p, x)
    want, g_want = jax.jit(jax.value_and_grad(plain, (0, 1)))(p, x)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name in p:
        np.testing.assert_allclose(g_got[0][name], g_want[0][name], rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(g_want[0][name]).max()),
                                   err_msg=name)
    np.testing.assert_allclose(g_got[1], g_want[1], rtol=2e-4,
                               atol=2e-5 * float(jnp.abs(g_want[1]).max()))
    # and the controls are other layers
    for control in ("drop_rope", "half_split" if interleave else None):
        other = jnp.stack([ref.mla(p, row, cfg, mm, ref.pairing(dict(cfg, rope_interleave=True),
                                                                  control))
                           for row in x]) if control else None
        if other is not None:
            mine = layer.apply(renamed(p), x, state={}, train=True, rng=None)[0]
            assert float(jnp.abs(other - mine).max()) > 3e-3 * float(jnp.abs(mine).max()), control


def parent_apply(self, params, x, mask=None):
    """`LatentAttention.apply` as the parent commit had it (PR 37), to the
    letter but for `device_scope` and the `REMAT_KEEP` tag on q, k, v (PR 48),
    which name and change nothing."""
    b, t, _ = x.shape
    h, nope = self.n_heads, self.nope_dim

    def heads(a):  # [b, t, h d] -> [b, h, t, d]
        return a.reshape(b, t, h, -1).transpose(0, 2, 1, 3)

    q = heads(ops.dot(x, params["Wq"]))
    ckr = ops.dot(x, params["Wkva"])
    c = hybrid.rms_norm(ckr[..., :self.kv_rank], params["kv_norm"], self.eps,
                        zero_centered=False)
    kv = heads(ops.dot(c, params["Wkvb"]))
    kr = jnp.broadcast_to(ckr[:, None, :, self.kv_rank:], (b, h, t, self.rope_dim))
    k = jnp.concatenate([kv[..., :nope], kr], axis=-1)
    o = att.attend(q, k, kv[..., nope:], causal=True, mask=mask)
    y = ops.dot(o.transpose(0, 2, 1, 3).reshape(b, t, h * self.v_dim), params["Wo"])
    if mask is not None:
        y = y * mask[..., None].astype(y.dtype)
    return y


@pytest.mark.parametrize("masked", [False, True])
def test_without_a_theta_the_layer_is_the_parents(masked, weights, rng):
    """Output AND jaxpr: `rope_theta=None` adds no operation and moves none
    (the three `name` equations of the tag apart: they lower to nothing)."""
    layer = latent()
    assert layer.rope_theta is None
    p = renamed(sub(weights, "l0.mla."))
    x = jnp.asarray(rng.standard_normal((2, T, 32)), F32)
    mask = jnp.asarray(np.arange(T)[None, :] < np.array([T, 50])[:, None], F32) if masked else None
    now = lambda q, x_: layer.apply(q, x_, state={}, train=True, rng=None, mask=mask)[0]  # noqa: E731
    then = lambda q, x_: parent_apply(layer, q, x_, mask)  # noqa: E731
    assert [e.primitive.name for e in jax.make_jaxpr(now)(p, x).eqns].count("name") == 3
    with mock.patch.object(hybrid, "checkpoint_name", lambda a, name: a):
        # a function object of its own: a trace is cached by it
        assert str(jax.make_jaxpr(lambda q, x_: now(q, x_))(p, x)) == str(jax.make_jaxpr(then)(p, x))
    np.testing.assert_array_equal(jax.jit(now)(p, x), jax.jit(then)(p, x))
    with_theta = latent(rope_theta=THETA)
    turned = jax.make_jaxpr(lambda q, x_: with_theta.apply(q, x_, state={}, train=True,
                                                           rng=None)[0])(p, x)
    assert len(turned.eqns) > len(jax.make_jaxpr(now)(p, x).eqns)


def test_a_shuffled_prefix_moves_the_last_token_only_with_rotary(weights, rng):
    """The last token sees every token: without positions its output does
    not depend on their order; with the rotation it does."""
    p = renamed(sub(weights, "l0.mla."))
    x = jnp.asarray(rng.standard_normal((1, T, 32)), F32)
    order = np.concatenate([rng.permutation(T - 1), [T - 1]])
    for theta, moves in ((None, False), (THETA, True)):
        layer = latent(rope_theta=theta)
        run = lambda x_: layer.apply(p, x_, state={}, train=True, rng=None)[0][0, -1]  # noqa: E731
        gap = float(jnp.abs(run(x) - run(x[:, order])).max() / jnp.abs(run(x)).max())
        assert (gap > 1e-3) == moves, (theta, gap)
        if not moves:
            assert gap < 1e-5


# ---------------------------------------------------------------------------
# the share of the experts
# ---------------------------------------------------------------------------
def test_eight_shares_add_up_to_the_uncut_layer(rng):
    """This recipe — sigmoid scores, top-6 of 128 by score + bias,
    renormalised x 2.448, two shared experts ungated — with each of 8 ranks
    holding 16 experts; what every rank computes alike (the shared experts)
    is counted once."""
    draw = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), F32)  # noqa: E731
    p = {"router": draw(32, 128), "select_bias": draw(128) * 0.1, "wgu": draw(128, 32, 16),
         "wd": draw(128, 8, 32), "shared_wgu": draw(32, 32), "shared_wd": draw(16, 32)}
    x = jnp.asarray(rng.standard_normal((2, 40, 32)), F32)
    cfg = dict(CFG, n_routed_experts=128, n_routed_experts_published=128, experts_first=0,
               num_experts_per_tok=6, moe_intermediate_size=8, n_shared_experts=2)
    assert cfg["routed_scaling_factor"] == 2.448 and cfg["norm_topk_prob"] is True
    mm = common.matmul(None)
    xf = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(p, xf, cfg, mm)
        shared = ref.swiglu(xf, p["shared_wgu"], p["shared_wd"], mm)
    total = shared
    for rank in range(8):
        layer = RoutedExperts(n_experts=128, top_k=6, expert_width=8, shared_width=16,
                              experts_held=(16 * rank, 16), capacity_factor=8.0,
                              norm_topk=True, scoring="sigmoid", routed_scale=2.448,
                              expert_act="swiglu", shared_gated=False)
        held = slice(16 * rank, 16 * rank + 16)
        mine = {"router": p["router"], "select_bias": p["select_bias"], "Wgu": p["wgu"][held],
                "Wd": p["wd"][held], "shared_Wgu": p["shared_wgu"], "shared_Wd": p["shared_wd"]}
        y, st = layer.apply(mine, x, state=layer.init_state(IN), train=True, rng=None)
        assert int(st["counters"]["dropped"]) == 0
        assert layer.capacity(80) == 80 * 6               # every assignment has a row
        total = total + (y.reshape(-1, 32) - shared)
    np.testing.assert_allclose(total, whole, atol=2e-5 * float(jnp.abs(whole).max()))


def test_reference_controls_change_the_result(weights, rng):
    x = jnp.asarray(rng.standard_normal((130, 32)), F32)
    blk = lambda i, op: jax.jit(lambda w, x_: ref.block(w, x_, CFG, i, op))(weights, x)  # noqa: E731
    for i, controls in ((0, ("drop_rope", "half_split", ref.CONTROL)),
                        (1, ("drop_rope", "half_split", "drop_expert", "drop_shared",
                             "ignore_bias", ref.CONTROL))):
        sound = blk(i, None)
        for control in controls:
            assert float(jnp.abs(blk(i, control) - sound).max()) > 1e-4, (i, control)


def test_the_seeded_weights_make_positions_matter():
    """`init_params` keeps channel 0 of the hidden state constant and lets
    the rope columns alone read it: the shared rope key part gets a
    token-independent vector and head h's rope query part the same vector
    turned back `look_back(h)` positions. The mean rope score by distance
    then peaks at look_back(0) = 1, evenly about it, and only under the
    published pairing. (A theta at which all eight pairs turn inside the 60
    tokens; at 1e6 the slow pairs keep every distance alike.)"""
    cfg = dict(CFG, hidden_size=64, qk_rope_head_dim=16, num_hidden_layers=1, rope_theta=30.0)
    w = ref.init_params(cfg, SEED)
    np.testing.assert_array_equal(w["embed"][:, 0], ref.CHANNEL)
    for name, leaf in w.items():                      # nothing else reads or writes the channel
        if name.endswith(ref.READS) and "mla.wq" not in name and "mla.wkva" not in name:
            assert not np.asarray(leaf)[..., 0, :].any(), name
        if name.endswith(ref.WRITES):
            assert not np.asarray(leaf)[..., 0].any(), name
    assert not np.asarray(w["l0.mla.wkva"])[0, :16].any()
    assert not np.asarray(w["l0.mla.wq"]).reshape(64, 4, 24)[0, :, :8].any()
    x = w["embed"][jnp.arange(60) % 48]
    x = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))
    kr = x @ w["l0.mla.wkva"][:, 16:]
    q = (x @ w["l0.mla.wq"]).reshape(60, 4, 8 + 16)[:, 0, 8:]          # head 0 looks 1 back
    assert ref.look_back(0) == 1
    at = {}
    for inter in (True, False):
        s = np.asarray(ref.rotate(q, 30.0, inter) @ ref.rotate(kr, 30.0, inter).T)
        at[inter] = [np.diagonal(s, -lag).mean() for lag in range(0, 40)]
    assert int(np.argmax(at[True])) == 1
    assert at[True][1] * 24 ** -0.5 == pytest.approx(ref.ROPE_LOGIT, rel=0.5)
    assert abs(at[True][0] - at[True][2]) < 0.02 * at[True][1]        # even about the distance
    assert abs(at[False][0] - at[False][2]) > 0.1 * at[True][1]       # the other pairing is not


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def test_every_layer_is_latent_and_the_first_is_dense():
    model = zoo.DeltaLatentMoELM(**ZOO_ARGS)
    assert model.sublayer_kinds() == [("latent", "dense"), ("latent", "experts"),
                                      ("latent", "experts")]
    subs = [l.sub for l in model.conf().layers if isinstance(l, SubLayerBlock)]
    assert [type(s) for s in subs] == [LatentAttention, GatedMLP, LatentAttention, RoutedExperts,
                                       LatentAttention, RoutedExperts]
    assert all(s.rope_theta == 1e6 and s.rope_interleave for s in subs[::2])
    assert ref.kinds(CFG) == ["dense", "moe", "moe"]
    shared = [s.shared_width for s in subs if isinstance(s, RoutedExperts)]
    assert shared == [2 * CFG["moe_intermediate_size"]] * 2        # two shared experts, one swiglu
    # the published depth: 48 latent layers, one dense
    kinds = zoo.DeltaLatentMoELM(**dict(ZOO_ARGS, num_hidden_layers=48)).sublayer_kinds()
    assert [m for m, _ in kinds] == ["latent"] * 48
    assert [f for _, f in kinds].count("dense") == 1
    # `mla_use_nope` (the kimi_linear shape) hands no theta on, whatever rope_theta says
    kimi = {k: v for k, v in tiny_kimi.kimi_linear()["program"]["args"].items() if k != "remat"}
    for args in (kimi, dict(ZOO_ARGS, mla_use_nope=True)):
        latent = [l.sub for l in zoo.DeltaLatentMoELM(**args).conf().layers
                  if isinstance(l, SubLayerBlock) and isinstance(l.sub, LatentAttention)]
        assert latent and all(s.rope_theta is None for s in latent)


def batches(n=3, rows=2):
    return tsi.make_batches(CFG, dict(tiny_ids.TRAIN_IDS, distinct_batches=n), rows, SEED)


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
    assert len(bias) == 2
    assert all(want["grad_norms"][k] == got["grad_norms"][k] == 0.0 for k in bias)
    assert all(want["delta_norms"][k] == got["delta_norms"][k] == 0.0 for k in bias)
    log_ = telemetry.fit_log()[-1]
    assert "kda" not in log_ and len(log_["experts"]) == 2      # no recurrent mixer here
    assert all(e["dropped_assignments"] == 0 and 0.0 < e["capacity_fill"] <= 1.0
               for e in log_["experts"])
    # a program that rotates nothing, or pairs the other way, is another model
    for control in ("drop_rope", "half_split"):
        other = tsi.reference_numbers(ref, CFG, p0, {}, data, 1, control)
        assert abs(other["losses"][0] - want["losses"][0]) > 1e-6, control


def test_lean_reference_steps_are_the_common_ones():
    """The reference's own `train_steps` (Adam a leaf at a time, float32
    under `jax_enable_x64`) against `common.train_steps`."""
    cfg = tiny_kanana.kanana(seq_len=40)
    data = tsi.make_batches(cfg, tiny_ids.TRAIN_IDS, 2, SEED)
    p0 = jax.device_get(ref.init_params(cfg, SEED))
    lean = tsi.reference_numbers(ref, cfg, p0, {}, data, 3)
    seq = [(b[0], b[2]) for b in data]
    plain = common.train_steps(ref, cfg, jax.device_put(p0), {}, seq)
    np.testing.assert_allclose(lean["losses"], plain["losses"], rtol=1e-6)
    for key in ("grad_norms", "delta_norms"):
        for leaf, v in plain[key].items():
            assert lean[key][leaf] == pytest.approx(v, rel=1e-4, abs=1e-9), (key, leaf)


def test_a_shuffled_sequence_changes_the_loss_only_with_rotary(rng):
    """zoo -> config DSL at one layer (latent attention + dense): the last
    token sees every token, so its logits and its loss keep their values under
    a shuffle of the tokens before it when the layer knows no positions, and
    change with the rotation. (From the second layer on the causal mask alone
    tells a token's inputs where they stood: asserted too.)"""
    ids = rng.integers(0, 48, (1, T))
    order = np.concatenate([rng.permutation(T - 1), [T - 1]])

    def last_loss(net, a):
        logits = np.asarray(net.output(jnp.asarray(a, jnp.int32)))[0, -1].astype(np.float64)
        return float(np.log(np.exp(logits - logits.max()).sum()) + logits.max() - logits[7])

    for nope, layers, moves in ((True, 1, False), (False, 1, True), (True, 3, True)):
        net = zoo.DeltaLatentMoELM(**dict(ZOO_ARGS, mla_use_nope=nope,
                                          num_hidden_layers=layers)).init()
        gap = abs(last_loss(net, ids) - last_loss(net, ids[:, order]))
        assert (gap > 1e-5) == moves, (nope, layers, gap)
        if not moves:
            assert gap < 1e-7, gap


def test_zoo_class_serialises_and_round_trips(tmp_path, rng):
    conf = zoo.DeltaLatentMoELM(**ZOO_ARGS).conf()
    again = MultiLayerConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    assert '"rope_theta": 1000000.0' in conf.to_json()
    net = MultiLayerNetwork(conf).init()
    ids = jnp.asarray(rng.integers(0, 48, (2, T)), jnp.int32)
    want = net.output(ids)
    path = str(tmp_path / "kanana.zip")
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


# ---------------------------------------------------------------------------
# the scope
# ---------------------------------------------------------------------------
def test_rope_is_a_part_and_the_lowered_step_names_it():
    assert "rope" in trace_mod.SCOPE_PARTS
    net = zoo.DeltaLatentMoELM(**ZOO_ARGS, remat="full").init()
    ids = jnp.zeros((2, T), jnp.int32)
    args = (net.params, net.state, net.opt_state, jnp.int32(0), jax.random.PRNGKey(0),
            ids, ids, None, None)
    text = jax.jit(net._train_step_fn()).trace(*args).lower().as_text(debug_info=True)
    for layer in (1, 3, 5):
        assert f"dl4j.L{layer}.sublayerblock/dl4j.latentattention/rope" in text, layer
    assert "transpose(jvp(" in text and "/rope/" in text           # and on the backward pass
    # the kimi_linear shape opens none
    kimi = zoo.DeltaLatentMoELM(**{k: v for k, v in tiny_kimi.kimi_linear()["program"]["args"].items()
                                   if k != "remat"}).init()
    kargs = (kimi.params, kimi.state, kimi.opt_state, *args[3:])
    assert "/rope" not in jax.jit(kimi._train_step_fn()).trace(*kargs).lower().as_text(
        debug_info=True)
