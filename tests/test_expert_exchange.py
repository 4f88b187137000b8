"""`RoutedExperts`' exchange between expert-parallel ranks (PR 52), on the
CPU's virtual devices: one layer over 4 ranks against the uncut plain layer
and against the sum of the four `experts_held` shares, values and every
gradient; an overflowing pair buffer, counted on the rank it overflowed on;
the same layer as a block under 'full' remat (PR 53: the recompute sends no
row again — what arrived is kept, the weights' gradient is taken where the
experts are); the Mellum-shaped `zoo.WindowedMoELM` under `ParallelWrapper(MeshSpec(data=4))`
against `benchmark/reference/mellum2.py` with its expert matrices and their
moments split at rest; and the same model on one device, where the layer
lowers to the path it always took."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.layers import RoutedExperts, hybrid
from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper
from deeplearning4j_tpu.parallel.mesh import build_mesh
from deeplearning4j_tpu.util.jaxcompat import remat_policy

D, F, E, RANKS, T = 32, 16, 8, 4, 24
IN = it.recurrent(D, T)


def mesh_of(n):
    return build_mesh(MeshSpec(data=n), jax.devices()[:n])


def layer_of(**args):
    return RoutedExperts(**{**dict(n_experts=E, top_k=3, expert_width=F, shared_width=0,
                                   capacity_factor=float(E)), **args})


def weights(rng, layer):
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(0.3 * rng.standard_normal(a.shape), jnp.float32),
        layer.init_params(jax.random.PRNGKey(0), IN))


def plain(layer, p, x):
    """The uncut layer in ten lines: every expert on every token, weighted by
    the router's (mostly zero) weight; the shared expert as it is."""
    xf = x.reshape(-1, D)
    top, idx = layer.route(p, xf)
    wt = jnp.zeros((xf.shape[0], E)).at[jnp.arange(xf.shape[0])[:, None], idx].add(top)
    act, _, up = layer._act()
    out = sum(wt[:, e:e + 1] * (act(xf @ p[up][e]) @ p["Wd"][e]) for e in range(E))
    if layer.shared_width:
        out = out + act(xf @ p["shared_" + up]) @ p["shared_Wd"]
    return out.reshape(x.shape)


def on_mesh(layer, p, x, mesh):
    specs = layer.partition_specs(p, dict(mesh.shape))
    put = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs,
                                 is_leaf=lambda n: isinstance(n, P))
    return jax.device_put(p, put), jax.device_put(x, NamedSharding(mesh, P("data")))


def step_of(layer, x, remat=False):
    """The jitted value and gradients of a weighted sum of the layer's output;
    `remat`: the layer as a block under the 'full' policy, as a model wraps it."""
    weigh = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def block(p_, x_):
        return layer.apply(p_, x_, state=layer.init_state(IN), train=True, rng=None)

    if remat:
        block = jax.checkpoint(block, policy=remat_policy("full"))

    def loss(p_, x_):
        y, st = block(p_, x_)
        return jnp.sum(y * weigh), (y, st)

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))


def summary(layer, state):
    """The layer's `fit_log()` entry for one step's counters."""
    return layer.counter_summary({k: np.atleast_1d(np.asarray(v))
                                  for k, v in state["counters"].items()})[1]


def value_and_grads(layer, p, x, mesh=None, remat=False):
    fn = step_of(layer, x, remat)
    if mesh is None:
        return fn(p, x)
    with jax.set_mesh(mesh):
        return fn(*on_mesh(layer, p, x, mesh))


CASES = {"softmax": {}, "sigmoid": dict(scoring="sigmoid", routed_scale=2.5),
         "shared": dict(shared_width=F, shared_gated=False), "relu2": dict(expert_act="relu2")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_exchange_is_the_uncut_layer_and_the_sum_of_the_shares(case, rng):
    args = CASES[case]
    whole, spread = layer_of(**args), layer_of(exchange_axis="data", capacity_factor=4.0, **args)
    p = weights(rng, whole)
    x = jnp.asarray(rng.standard_normal((RANKS, T, D)), jnp.float32)
    (_, (y1, st1)), (gp1, gx1) = value_and_grads(whole, p, x)
    (_, (y4, st4)), (gp4, gx4) = value_and_grads(spread, p, x, mesh_of(RANKS))
    want = plain(whole, p, x)
    tol = 2e-5 * float(jnp.abs(want).max())
    np.testing.assert_allclose(y1, want, atol=tol)
    np.testing.assert_allclose(y4, want, atol=tol)
    # the four shares: each rank alone adds its own experts' terms, the shared one once
    held, up = E // RANKS, whole._act()[2]
    shared = plain(layer_of(**args), {**p, up: 0 * p[up]}, x)      # what every rank adds alike
    total = shared
    for r in range(RANKS):
        share = layer_of(experts_held=(r * held, held), **args)
        mine = {**p, up: p[up][r * held:(r + 1) * held], "Wd": p["Wd"][r * held:(r + 1) * held]}
        y, st = share.apply(mine, x, state=share.init_state(IN), train=True, rng=None)
        assert int(st["counters"]["dropped"]) == 0
        total = total + (y - shared)
    np.testing.assert_allclose(total, want, atol=tol)
    # every gradient, and where it rests: an expert's on its rank, the others' whole
    for name in gp1:
        scale = float(jnp.abs(gp1[name]).max()) or 1.0
        np.testing.assert_allclose(gp4[name], gp1[name], atol=2e-5 * scale, err_msg=name)
        rows = {s.data.shape[0] for s in gp4[name].addressable_shards}
        assert rows == ({held} if name in (up, "Wd") else {gp1[name].shape[0]}), name
    np.testing.assert_allclose(gx4, gx1, atol=2e-5 * float(jnp.abs(gx1).max()))
    # the counters are sums over the ranks
    c1, c4 = st1["counters"], st4["counters"]
    np.testing.assert_array_equal(c4["load"], c1["load"])
    assert int(c4["dropped"]) == 0 and int(c4["steps"]) == 1
    pair = spread.pair_rows(T, RANKS)
    assert int(c4["capacity"]) == RANKS * RANKS * pair
    assert int(c4["pair_fill_hist"].sum()) == 1
    entry = summary(spread, st4)
    assert 0.0 < entry["pair_fill_max"] <= 1.0 and entry["rank_load_max_over_mean"] >= 1.0
    # rows out and back and their cotangents; an expert id, a weight and a dot product a row
    assert entry["exchange_bytes"] == (RANKS - 1) * pair * (4 * D * 4 + 4 + 2 * 4)


def overflowing(rng, monkeypatch, pair):
    """A layer, weights and tokens whose pair (2 -> 0) holds 2 T assignments
    for `pair` rows: rank 2's tokens all choose expert 0, then expert 1."""
    monkeypatch.setattr(RoutedExperts, "pair_rows", lambda self, rows, ranks: pair)
    layer = layer_of(top_k=2, exchange_axis="data")
    p = weights(rng, layer)
    p["router"] = 0.01 * p["router"]
    x = np.asarray(rng.standard_normal((RANKS, T, D)), np.float32)
    x[:, :, 0] = 0.0
    x[2, :, 0] = 30.0                                   # rank 2: feature 0 decides
    p["router"] = p["router"].at[0, 0].set(2.0).at[0, 1].set(1.0)
    return layer, p, jnp.asarray(x)


@pytest.mark.parametrize("pair", [T, 2 * T - 8])
def test_an_overflowing_pair_buffer_drops_on_its_rank_and_is_counted(pair, rng, monkeypatch):
    """Rank 2's tokens all choose experts 0 and 1 — rank 0's: the pair (2 -> 0)
    holds 2 T assignments. With `pair` rows a pair the buffer keeps the first
    `pair` in expert order (expert 0's T, then expert 1's) and drops the rest;
    no other pair overflows."""
    layer, p, x = overflowing(rng, monkeypatch, pair)
    (_, (y, st)), _ = value_and_grads(layer, p, x, mesh_of(RANKS))
    top, idx = layer.route(p, x[2])
    assert set(np.asarray(idx).ravel()) == {0, 1}
    dropped = 2 * T - pair
    c = st["counters"]
    assert int(c["dropped"]) == dropped and int(c["load"].sum()) == RANKS * T * 2
    filled = np.flatnonzero(np.asarray(c["pair_fill_hist"]))
    assert filled.tolist() == [min(2 * T * hybrid.FILL_BINS // pair, 2 * hybrid.FILL_BINS - 1)]
    want = plain(layer, p, x)
    tol = 2e-5 * float(jnp.abs(want).max())
    for r in (0, 1, 3):                                 # nothing of theirs was cut
        np.testing.assert_allclose(y[r], want[r], atol=tol)
    # rank 2: expert 1's term is there for its first pair - T tokens alone
    act = layer._act()[0]
    terms = [top[:, j:j + 1] * (act(x[2] @ p["Wgu"][e]) @ p["Wd"][e])
             for j, e in ((0, 0), (1, 1))]
    assert np.all(np.asarray(idx)[:, 0] == 0)           # expert 0 first for every token
    kept = (jnp.arange(T) < pair - T)[:, None]
    np.testing.assert_allclose(y[2], terms[0] + jnp.where(kept, terms[1], 0.0), atol=tol)


# ---------------------------------------------------------------------------
# a block under 'full' remat (PR 53)
# ---------------------------------------------------------------------------
def close(got, want, err_msg=""):
    np.testing.assert_allclose(got, want, atol=2e-5 * (float(jnp.abs(want).max()) or 1.0),
                               err_msg=err_msg)


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_checkpointed_block_is_the_block_and_the_uncut_layer(case, rng):
    """The exchanging layer as a block under `jax.checkpoint(policy='full')`:
    its values and EVERY gradient — the router's, which reaches it through the
    weights' gradient taken on the expert side, included — are those of the
    same layer without the checkpoint and of the uncut layer on one device."""
    args = CASES[case]
    whole, spread = layer_of(**args), layer_of(exchange_axis="data", capacity_factor=4.0, **args)
    p = weights(rng, whole)
    x = jnp.asarray(rng.standard_normal((RANKS, T, D)), jnp.float32)
    (_, (y1, _)), (gp1, gx1) = value_and_grads(whole, p, x)
    (_, (y4, _)), (gp4, gx4) = value_and_grads(spread, p, x, mesh_of(RANKS))
    (_, (yr, st)), (gpr, gxr) = value_and_grads(spread, p, x, mesh_of(RANKS), remat=True)
    assert int(st["counters"]["dropped"]) == 0
    for want_y, want_gp, want_gx in ((y4, gp4, gx4), (y1, gp1, gx1)):
        close(yr, want_y)
        close(gxr, want_gx)
        assert set(gpr) == set(want_gp)
        for name in want_gp:
            close(gpr[name], want_gp[name], name)


@pytest.mark.parametrize("case", ["softmax", "sigmoid"])
def test_the_recompute_sends_no_row_across(case, rng):
    """The compiled step of a checkpointed block holds FOUR `all-to-all`s of
    the row buffer's shape — out and back forward, their two cotangents
    backward — and none under `rematted_computation` (six before PR 53, two of
    them the recompute's); the backward's weights and dot products cross as a
    scalar a row; and the recompute neither buckets nor permutes a row buffer."""
    spread = layer_of(exchange_axis="data", capacity_factor=4.0, **CASES[case])
    p = weights(rng, spread)
    x = jnp.asarray(rng.standard_normal((RANKS, T, D)), jnp.float32)
    mesh, pair = mesh_of(RANKS), spread.pair_rows(T, RANKS)
    with jax.set_mesh(mesh):
        args = on_mesh(spread, p, x, mesh)
        text = step_of(spread, x, remat=True).lower(*args).compile().as_text()
        plain_text = step_of(spread, x).lower(*args).compile().as_text()
    sends = [line for line in text.splitlines() if re.search(r" all-to-all(-start)?\(", line)]
    assert sends and not [s for s in sends if "rematted_computation" in s], sends
    # XLA:CPU writes an `all-to-all` over 4 ranks as a tuple of 4 parts: the first part's type
    kinds = [re.search(r"= \(?(\w+)\[\d+,([\d,]+)\]", s).groups() for s in sends]
    assert kinds.count(("f32", f"{pair},{D}")) == 4, kinds
    # the ids out; the weights out and the dot products home in the backward
    assert kinds.count(("s32", f"{pair}")) == 1 and kinds.count(("f32", f"{pair}")) == 2, kinds
    remade = [line for line in text.splitlines() if "rematted_computation" in line
              and ("/bucket/gather" in line or "/gather/gather" in line)
              and f"f32[{RANKS * pair},{D}]" in line.split("=", 1)[1][:40]]
    assert not remade, remade
    # the same step without the checkpoint sends the same seven
    assert len(re.findall(r" all-to-all(?:-start)?\(", plain_text)) == len(sends) == 7


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("pair", [T, 2 * T - 8])
def test_a_cut_assignment_weighs_nothing_and_learns_nothing(pair, remat, rng, monkeypatch):
    """The overflowing pair (2 -> 0) again, with a factor on every
    assignment's weight (`weigh` [tokens, top_k], ones) whose gradient is the
    assignment's weight gradient: exactly 0 for the 2 T - `pair` assignments
    the buffer cut — their rows never crossed, and the row their clipped index
    points at is another assignment's — and the uncut layer's for every other."""
    layer, p, x = overflowing(rng, monkeypatch, pair)
    real = RoutedExperts.route

    def route(self, p_, xf):
        top, idx = real(self, p_, xf)
        w = p_["weigh"]
        if w.shape[0] != xf.shape[0]:       # inside the island: this rank's tokens
            w = lax.dynamic_slice_in_dim(w, lax.axis_index("data") * xf.shape[0], xf.shape[0])
        return top * w, idx

    monkeypatch.setattr(RoutedExperts, "route", route)
    p["weigh"] = jnp.ones((RANKS * T, 2), jnp.float32)
    uncut = layer_of(top_k=2)
    _, (gp1, _) = value_and_grads(uncut, p, x)
    (_, (_, st)), (gp4, _) = value_and_grads(layer, p, x, mesh_of(RANKS), remat=remat)
    assert int(st["counters"]["dropped"]) == 2 * T - pair
    cut = np.zeros((RANKS, T, 2), bool)
    cut[2, pair - T:, 1] = True             # rank 2, expert 1 (its second slot), the last tokens
    got, want = np.asarray(gp4["weigh"]).reshape(cut.shape), np.asarray(gp1["weigh"]).reshape(cut.shape)
    assert np.all(got[cut] == 0.0) and np.all(want[cut] != 0.0)
    close(got[~cut], want[~cut])
    # and the experts' and the router's gradients hold no term of a cut assignment
    kept = jnp.asarray(~cut, jnp.float32).reshape(-1, 2)
    _, (gp1_cut, _) = value_and_grads(uncut, {**p, "weigh": kept}, x)
    for name in ("Wgu", "Wd", "router"):
        close(gp4[name], gp1_cut[name], name)


@pytest.mark.parametrize("devices", [RANKS, 1])
def test_what_arrived_is_counted_as_kept(devices, rng):
    """`exchange_kept_mb`: the MB of arrived rows — `ranks` x `pair_rows` x
    the buffer's width a rank — tagged `REMAT_KEEP` as the step was traced;
    0.0 where the axis has one rank and nothing crosses."""
    layer = layer_of(exchange_axis="data", capacity_factor=4.0)
    p = weights(rng, layer)
    x = jnp.asarray(rng.standard_normal((RANKS, T, D)), jnp.float32)
    (_, (_, st)), _ = value_and_grads(layer, p, x, mesh_of(devices), remat=True)
    entry = summary(layer, st)
    arrived = RANKS * layer.pair_rows(T, RANKS) * D * 4 / 1e6
    assert entry["exchange_kept_mb"] == (arrived if devices > 1 else 0.0)
    assert (entry["exchange_bytes"] > 0) == (devices > 1)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def mellum_case():
    from benchmark.reference import mellum2 as ref
    from benchmark.tests import tiny_mellum2
    from benchmark.traffic import train_stream_ids as tsi

    cfg = tiny_mellum2.mellum2()    # 4 heads over 2 key/value heads, window 8, t 32, 8 experts top-2
    seed = 2 ** 31 + 52
    data = tsi.make_batches(cfg, dict(tiny_mellum2.TRAIN_IDS_MESH, distinct_batches=2), 4, seed)
    p0 = jax.device_get(ref.init_params(cfg, seed))
    want = tsi.reference_numbers(ref, cfg, p0, {}, data, 2)
    return cfg, ref, data, p0, want


LIMITS = {"loss_gap": 2e-6, "grad_norm_gap": 2e-4, "grad_norm_gap_median": 2e-5,
          "delta_norm_gap": 2e-4}


@pytest.mark.parametrize("devices", [4, 1])
def test_mellum_shape_takes_the_references_adam_steps(devices, mellum_case):
    """zoo.WindowedMoELM without gate, shared expert and dense layer -> config
    DSL -> `ParallelWrapper(MeshSpec(data=devices)).fit` on integer labels
    against benchmark/reference/mellum2.py (every expert on every token,
    float32): each loss, the first gradient of EVERY leaf as Adam got it, the
    parameters' change after two steps. Over 4 devices the expert matrices
    and both their moments rest split 4 ways and everything else is the same
    on every device; on 1 the exchange axis has one rank and the layer runs as
    it does with every expert here — the same numbers."""
    from benchmark import program
    from benchmark.reference import common
    from benchmark.traffic import train_stream as ts
    from deeplearning4j_tpu import telemetry

    cfg, ref, data, p0, want = mellum_case
    net = program.build_net(cfg)
    program.install(net, ref, cfg, p0, {})
    paths = ref.program_paths(cfg)
    assert len(jax.tree_util.tree_leaves(net.params)) == len(paths) == 4 * 7 + 3
    log = ts.StepLog()
    net.set_listeners(log)
    pw = ParallelWrapper(net, mesh=mesh_of(devices))
    stream = ts.make_stream([program.dataset(x, y) for x, y, _ in data], 4)
    got = ts.program_numbers(net, pw, stream, log, ref, cfg, p0, 2)
    rows = common.compare_training(got, want, LIMITS, ref.COMPARISONS)
    assert all(r[3] for r in rows), rows
    gaps = common.leaf_gaps(got["grad_norms"], want["grad_norms"])
    assert set(gaps) == set(paths) and max(gaps.values()) < 2e-4, gaps
    experts = telemetry.fit_log()[-1]["experts"]
    assert len(experts) == 4 and all(e["dropped_assignments"] == 0 for e in experts)
    assert all((e["exchange_bytes"] > 0) == (devices > 1) for e in experts)
    for i in range(1, 9):
        for tree in (net.params[f"layer_{i}"], net.opt_state[i]["m"], net.opt_state[i]["v"]):
            for name, leaf in tree["sub"].items():
                split = devices > 1 and name in ("Wgu", "Wd")
                shards = leaf.addressable_shards
                assert len(shards) == devices
                assert {s.data.shape[0] for s in shards} == {
                    leaf.shape[0] // devices if split else leaf.shape[0]}, (i, name)
                if not split:       # whole and the same everywhere
                    assert all(np.array_equal(s.data, shards[0].data) for s in shards), (i, name)


def test_without_the_axis_the_step_is_the_one_it_was(rng):
    """A layer that names an exchange axis lowers, where no mesh has the axis,
    to the jaxpr of the layer that names none (the state's two more counters
    apart)."""
    x = jnp.asarray(rng.standard_normal((2, T, D)), jnp.float32)
    texts = []
    for axis in (None, "data"):
        layer = layer_of(exchange_axis=axis)
        p, state = weights(np.random.default_rng(3), layer), layer.init_state(IN)

        def out(p_, x_, layer=layer, state=state):
            return layer.apply(p_, x_, state=state, train=False, rng=None)[0]

        texts.append(str(jax.make_jaxpr(jax.grad(lambda p_, x_: jnp.sum(out(p_, x_))))(p, x)))
    assert texts[0] == texts[1]
    with pytest.raises(ValueError, match="holds a share"):
        dataclasses.replace(layer_of(exchange_axis="data"), experts_held=(0, 4)).init_params(
            jax.random.PRNGKey(0), IN)
