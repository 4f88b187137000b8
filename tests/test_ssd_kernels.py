"""The state-space rule's Pallas kernel pair (`ops/ssd_kernels.py`,
interpreted here) behind its door `ops.delta.ssd_chunks`: outputs, the states
the chunks start from and every input's gradient against the XLA form
(`ssm.ssd_chunked`) and against the token-by-token recurrence — at lengths
that are and are not a multiple of the chunk, decays near 0.2 and near 0.999
a token, one and eight groups, one and eight heads a group, masked tokens; a
lost carry; the policy's precision; the door's rule; that a `Mamba2Mixer`
step traced for a TPU holds both kernels under names that carry their shape
and no loop over the chunks; and the layer with its rows mapped, on the
kernel path."""
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h as ref
from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.layers import Mamba2Mixer, hybrid, ssm
from deeplearning4j_tpu.ops import chunk_kernels, delta, kernel_call, ssd_kernels
from deeplearning4j_tpu.ops import pallas_kernels as pk

F32 = jnp.float32
C, S = ssd_kernels.CHUNK, 128


def draw(rng, b, t, g, e, p, weakest, valid=None):
    """x [b, t, g e, p], dt [b, t, g e], a [g e], B and C [b, t, g, S],
    float32: a in -[1, 16] as the layer draws it, dt so that the per-token
    decay exp(dt a) is log-uniform between `weakest` and 0.9999. Row i's
    tokens from valid[i] on are masked: dt = 0."""
    h = g * e
    x = rng.standard_normal((b, t, h, p))
    a = -rng.uniform(1.0, 16.0, (h,))
    dt = np.exp(rng.uniform(np.log(1e-4), np.log(-np.log(weakest)), (b, t, h))) / -a
    bm, cm = 0.3 * rng.standard_normal((b, t, g, S)), 0.3 * rng.standard_normal((b, t, g, S))
    if valid is not None:
        dt = dt * (np.arange(t)[None, :] < np.asarray(valid)[:, None])[..., None]
    return tuple(jnp.asarray(m, F32) for m in (x, dt, a, bm, cm))


def through(rule):
    """BTF arrays -> (y [b, t, h, p], the chunk-start states
    [n, b, h, p, S]) by a chunk rule."""
    def f(x, dt, a, b, c):
        y, st = rule(*(hybrid.to_chunks(m, C) for m in (x, dt)), a,
                     *(hybrid.to_chunks(m, C) for m in (b, c)))
        return hybrid.from_chunks(y, x.shape[1]), st
    return f


kernels = through(lambda *a: delta.ssd_chunks(*a, impl="pallas"))
xla_form = through(ssm.ssd_chunked)


def token_by_token(x, dt, a, b, c):
    rep = lambda m: jnp.repeat(m, x.shape[2] // b.shape[2], axis=2)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        return jnp.stack([ref.ssm_recurrence(*row) for row in zip(x, dt, [a] * len(x), rep(b), rep(c))])


def with_gradients(f, ct):
    return jax.jit(lambda *a: (f(*a), jax.grad(
        lambda *a_: jnp.sum(f(*a_) * ct), tuple(range(5)))(*a)))


#: (t, groups, heads a group, head width, the weakest per-token decay,
#: masked): whole chunks and lengths that are not; decays near 1 and down to
#: 0.2 a token (e^-200 over a chunk); one and eight groups; one and eight
#: heads a group; the cell's 64-wide heads and narrower ones
CASES = [(256, 1, 8, 16, 0.999, False), (200, 8, 1, 16, 0.2, False), (300, 1, 1, 64, 0.2, True),
         (130, 2, 8, 64, 0.9, True), (128, 8, 8, 16, 0.2, False), (70, 2, 3, 32, 0.999, False)]


@pytest.mark.parametrize("t,g,e,p,weakest,masked", CASES)
def test_kernels_are_the_xla_form_and_the_token_recurrence(t, g, e, p, weakest, masked, rng):
    """Outputs, chunk-start states and all five gradients."""
    args = draw(rng, 2, t, g, e, p, weakest, valid=(t - 37, 30) if masked else None)
    ct = jnp.asarray(rng.standard_normal((2, t, g * e, p)), F32)
    first = lambda f: lambda *a: f(*a)[0]  # noqa: E731
    got, g_got = with_gradients(first(kernels), ct)(*args)
    assert np.all(np.isfinite(got))
    for name, oracle in (("xla form", first(xla_form)), ("recurrence", token_by_token)):
        want, g_want = with_gradients(oracle, ct)(*args)
        np.testing.assert_allclose(got, want, atol=3e-5 * float(jnp.abs(want).max()), rtol=2e-4,
                                   err_msg=name)
        for leaf, u, v in zip(("x", "dt", "a", "b", "c"), g_got, g_want):
            assert np.all(np.isfinite(u)), leaf
            np.testing.assert_allclose(u, v, atol=1e-4 * float(jnp.abs(v).max()) + 1e-7,
                                       rtol=1e-3, err_msg=f"{name}: d{leaf}")
    st, st_want = kernels(*args)[1], xla_form(*args)[1]
    assert st.shape == st_want.shape == (-(-t // C), 2, g * e, p, S)
    np.testing.assert_allclose(st, st_want, atol=3e-5 * float(jnp.abs(st_want).max()) + 1e-7)


def test_a_masked_token_writes_nothing_and_keeps_the_state(rng):
    """Past a row's valid length dt = 0: the outputs of the tokens before
    are those of the row cut there, the state a later chunk starts from is
    the one the last valid token left, and no gradient reaches what the
    masked tokens hold."""
    t, cut = 300, 70
    args = draw(rng, 1, t, 2, 2, 16, 0.9, valid=(cut,))
    (got, st), (short, _) = kernels(*args), kernels(*(m if m.ndim == 1 else m[:, :cut] for m in args))
    np.testing.assert_allclose(got[:, :cut], short, atol=1e-6)
    np.testing.assert_array_equal(st[1], st[2])
    assert np.any(np.asarray(st[1]))
    dx = jax.grad(lambda x: jnp.sum(kernels(x, *args[1:])[0] ** 2))(args[0])
    assert not np.any(np.asarray(dx[:, cut:])) and np.any(np.asarray(dx[:, :cut]))


def test_a_lost_carry_shows(rng, monkeypatch):
    """With decays near one the state a chunk starts from matters: kernels
    whose every chunk believes itself the row's first are the reference's
    "drop_carry" control, values and states."""
    t = 3 * C
    args = draw(rng, 1, t, 1, 2, 16, 0.999)
    sound, st = kernels(*args)
    monkeypatch.setattr(ssd_kernels.pl, "program_id", lambda axis: 0)
    broken, st_broken = kernels(*args)
    with jax.default_matmul_precision("highest"):
        rep = lambda m: jnp.repeat(m, 2, axis=2)[0]  # noqa: E731
        row = (args[0][0], args[1][0], args[2], rep(args[3]), rep(args[4]))
        control, whole = ref.ssm_recurrence(*row, chunk=C), ref.ssm_recurrence(*row)
    scale = float(jnp.abs(whole).max())
    np.testing.assert_allclose(sound[0], whole, atol=3e-5 * scale)
    np.testing.assert_allclose(broken[0], control, atol=3e-5 * scale)
    assert float(jnp.abs(broken - sound)[0, C:].max()) > 0.05 * scale
    assert np.any(np.asarray(st[1:])) and not np.any(np.asarray(st_broken))


@pytest.mark.parametrize("full", [False, True])
def test_the_kernels_run_at_the_policys_precision(full, rng):
    """`linear._precision()` decides, as for the XLA form's `_mm`: the
    default (one MXU pass on a TPU) or, under `dtypes.full_precision()`, the
    highest for every product."""
    x, dt, a, b, c = draw(rng, 1, C, 1, 2, 16, 0.9)
    args = (*(hybrid.to_chunks(m, C) for m in (x, dt)), a, *(hybrid.to_chunks(m, C) for m in (b, c)))
    with mock.patch.object(ssd_kernels, "ssd_chunk_kernels", wraps=ssd_kernels.ssd_chunk_kernels) as ran:
        if full:
            with dtypes.full_precision():
                delta.ssd_chunks(*args, impl="pallas")
        else:
            delta.ssd_chunks(*args, impl="pallas")
    assert ran.call_args.args[5:] == (full, True)      # (highest, interpret)


def test_the_decays_sums_are_exact_and_every_exponent_is_at_most_zero(rng):
    """dt a [heads, c] x the triangle through three bfloat16 passes is
    float32's running sum, also at a sum of -200 where ONE bf16 pass would be
    off by 0.06; the columns are the rows bit for bit, so that
    the diagonal's exponent is 0 and no exponent is above it."""
    adt = jnp.asarray(-rng.uniform(1.0, 2.2, (8, C)), F32)
    tri = jnp.asarray(chunk_kernels._pairs(C)[0], jnp.bfloat16)
    lower, diag = ssd_kernels._pair_masks(C)
    g, gcol, since, to_end, decay, last = ssd_kernels._decays(adt, tri, diag, False)
    want = np.cumsum(np.asarray(adt, np.float64), axis=1)
    assert want.min() < -190.0
    np.testing.assert_allclose(g, want, atol=4e-5)
    np.testing.assert_array_equal(gcol, g.T)
    one_pass = jnp.dot(adt.astype(jnp.bfloat16), tri.T, preferred_element_type=F32)
    assert float(jnp.abs(one_pass - want).max()) > 0.02
    for j in range(8):
        lt = ssd_kernels._decay_t(g[j:j + 1], gcol[:, j:j + 1], lower)
        assert float(lt.max()) == 1.0 and np.all(np.asarray(jnp.diagonal(lt)) == 1.0)
        assert not np.any(np.asarray(jnp.tril(lt, -1)))
    assert float(jnp.concatenate([since, to_end], 1).max()) <= 1.0 and float(decay.max()) < 1e-80


DOOR = [  # (impl, on tpu, shape of x, groups, state, dtype, rows a device) -> which
    ("auto", True, (64, 1, 64, 128, 64), 8, 128, F32, 1, "pallas"),    # the cell's
    ("auto", True, (4, 2, 8, 128, 16), 8, 256, F32, 2, "pallas"),      # one head a group, two state tiles
    ("auto", True, (4, 1, 8, 128, 128), 1, 128, F32, 1, "pallas"),
    ("auto", False, (64, 1, 64, 128, 64), 8, 128, F32, 1, "xla"),
    ("pallas", False, (2, 1, 2, 128, 16), 1, 128, F32, 1, "pallas"),
    ("xla", True, (64, 1, 64, 128, 64), 8, 128, F32, 1, "xla"),
    ("auto", True, (64, 1, 64, 128, 64), 8, 128, jnp.bfloat16, 1, "xla"),
    ("auto", True, (32, 1, 64, 256, 64), 8, 128, F32, 1, "xla"),       # another chunk
    ("auto", True, (128, 1, 64, 64, 64), 8, 128, F32, 1, "xla"),
    ("auto", True, (64, 1, 64, 128, 8), 8, 128, F32, 1, "xla"),        # half a packed sublane tile
    ("auto", True, (64, 1, 64, 128, 64), 8, 64, F32, 1, "xla"),        # half a lane tile of state
    ("auto", True, (64, 1, 64, 128, 64), 6, 128, F32, 1, "xla"),       # 6 does not divide 64
    ("auto", True, (64, 1, 64, 128, 64), 4, 128, F32, 1, "xla"),       # 16 heads a group
    ("auto", True, (64, 3, 64, 128, 64), 8, 128, F32, 0, "xla"),       # rows do not split over the mesh
    ("pallas", True, (2, 1, 2, 32, 8), 2, 16, F32, 1, "xla"),          # the tiny configurations'
]


@pytest.mark.parametrize("impl,tpu,shape,g,s,dtype,rows,want", DOOR)
def test_the_door_takes_what_the_kernels_are_written_for(impl, tpu, shape, g, s, dtype, rows, want,
                                                         monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu" if tpu else "cpu")
    monkeypatch.setattr(kernel_call, "per_device_batch", lambda b: rows)
    x = jax.ShapeDtypeStruct(shape, dtype)
    b = jax.ShapeDtypeStruct(shape[:2] + (g, shape[3], s), dtype)
    assert delta.ssd_impl(impl, x, b) == want
    monkeypatch.setenv("DL4J_TPU_PALLAS", "0")         # the helpers' switch turns 'auto' off
    assert delta.ssd_impl(impl, x, b) == (want if impl == "pallas" else "xla")


def layer_and_input(rng, b, t, g=1, e=2, p=16):
    layer = Mamba2Mixer(n_heads=g * e, head_dim=p, n_groups=g, state_dim=S, chunk=C)
    f = 32
    params = layer.init_params(jax.random.PRNGKey(3), it.recurrent(f, t))
    return layer, params, jnp.asarray(rng.standard_normal((b, t, f)), F32), it.recurrent(f, t)


def test_a_declined_call_returns_none_and_the_layer_keeps_its_xla_form(rng):
    x, dt, a, b, c = draw(rng, 1, C, 1, 2, 16, 0.9)
    args = (*(hybrid.to_chunks(m, C) for m in (x, dt)), a, *(hybrid.to_chunks(m, C) for m in (b, c)))
    assert delta.ssd_chunks(*args) is None             # 'auto' on the CPU
    assert delta.ssd_chunks(args[0][..., :8], *args[1:], impl="pallas") is None
    layer, params, x, itype = layer_and_input(rng, 2, C)
    with mock.patch.object(ssd_kernels, "ssd_chunk_kernels", wraps=ssd_kernels.ssd_chunk_kernels) as ran, \
            mock.patch.object(ssm, "ssd_chunked", wraps=ssm.ssd_chunked) as xla:
        layer.apply(params, x, state=layer.init_state(itype), train=True, rng=None)
    assert ran.call_count == 0 and xla.call_count == 1


def test_under_a_data_mesh_each_device_runs_its_own_rows(rng):
    """The kernels inside ONE manual region over 'data', rows (axis 1 of the
    chunk-major arrays) split over the devices, a whole on each: outputs,
    states and gradients are the unsharded call's — a's summed over the
    devices — and the results stay sharded by rows."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.parallel import MeshSpec, build_mesh

    if jax.device_count() < 8:
        pytest.skip("needs the 8 virtual devices of tests/conftest.py")
    x, dt, a, b, c = draw(rng, 8, 150, 1, 2, 16, 0.5)
    args = (*(hybrid.to_chunks(m, C) for m in (x, dt)), a, *(hybrid.to_chunks(m, C) for m in (b, c)))
    f = lambda *q: delta.ssd_chunks(*q, impl="pallas")  # noqa: E731
    grads = lambda *q: jax.grad(lambda *q_: jnp.sum(f(*q_)[0] ** 2), tuple(range(5)))(*q)  # noqa: E731
    want, g_want = jax.jit(f)(*args), jax.jit(grads)(*args)
    mesh = build_mesh(MeshSpec(data=8))
    with jax.set_mesh(mesh):
        put = tuple(jax.device_put(m, NamedSharding(mesh, P() if m.ndim == 1 else P(None, "data")))
                    for m in args)
        got, g_got = jax.jit(f)(*put), jax.jit(grads)(*put)
        assert jax.jit(f).lower(*put).as_text().count("sdy.manual_computation") == 1
    assert got[0].sharding.spec == got[1].sharding.spec == P(None, "data")
    for u, v in zip(got + g_got, want + g_want):
        np.testing.assert_allclose(u, v, atol=1e-5 * float(jnp.abs(v).max()) + 1e-8)


def test_a_tpu_step_holds_both_kernels_by_name_and_no_loop_over_the_chunks(rng):
    """Traced for a TPU (`jax.export`, nothing compiled), a Mamba2Mixer
    layer's forward + backward holds `dl4j_ssd_fwd` (twice: the row groups'
    checkpoint reruns it) and `dl4j_ssd_bwd`, their shape in the name — the
    counter that says the mechanism engaged: a kernel chosen while the step
    is traced runs in every step or in none — and no `while` that carries
    the state [rows, h, p, s] from chunk to chunk, which the XLA form's scan
    is on the CPU."""
    layer, params, x, itype = layer_and_input(rng, 2, 2 * C)

    def loss(p, x_):
        y, _ = layer.apply(p, x_, state=layer.init_state(itype), train=True, rng=None)
        return jnp.sum(y)

    with mock.patch("jax.default_backend", return_value="tpu"), \
            mock.patch.object(Mamba2Mixer, "CORE_BYTES", 2 * C * (2 * 16 + 2 * S) * 4):
        text = jax.export.export(jax.jit(jax.grad(loss)), platforms=["tpu"])(params, x).mlir_module()
    shape = "n2_r1_h2g1_c128_p16s128_float32"
    assert text.count(f"dl4j_ssd_fwd_{shape}") >= 2 and f"dl4j_ssd_bwd_{shape}" in text
    state = "tensor<1x2x16x128xf32>"
    carried = lambda t: any(state in line for line in t.splitlines() if "stablehlo.while" in line)  # noqa: E731
    assert not carried(text)
    # on the CPU the same layer keeps the XLA form, scan and all
    with mock.patch.object(Mamba2Mixer, "CORE_BYTES", 2 * C * (2 * 16 + 2 * S) * 4):
        cpu = jax.export.export(jax.jit(jax.grad(loss)), platforms=["cpu"])(params, x).mlir_module()
    assert "dl4j_ssd" not in cpu and carried(cpu)


@pytest.mark.parametrize("part", ["fwd", "bwd"])
def test_the_benchmarks_trace_reader_folds_a_steps_calls_into_the_family(part):
    """The cell's kernels under their names as a trace holds them (one
    instruction a call site): `device_ops` adds them up under the family."""
    from benchmark import trace_reduce

    x = jax.ShapeDtypeStruct((64, 1, 8, 8 * 64, C), F32)
    dt, b = jax.ShapeDtypeStruct((64, 1, 8, 8, C), F32), jax.ShapeDtypeStruct((64, 1, 8, C, S), F32)
    name = pk.kernel_name(f"ssd_{part}", F32, **ssd_kernels._names(x, dt, b))
    assert name == f"dl4j_ssd_{part}_n64_r1_h64g8_c128_p64s128_float32"
    for site in (".41", ".43"):
        event = f"%{name}{site} = f32[64,1,8,512,128]{{4,3,2,1,0}} custom-call(bf16[128,128] %a)"
        assert trace_reduce.describe(event) == f"dl4j_ssd_{part}"
