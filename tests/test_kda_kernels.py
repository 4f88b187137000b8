"""The per-channel delta rule's Pallas kernel pair (`ops/kda_kernels.py`,
interpreted here) behind its door `ops.delta.kda_chunks`: outputs, the
chunk-start states and every input's gradient against the XLA form
(`hybrid.chunk_channel_gated_delta_rule`) and against the token-by-token
recurrence — at a length that is and one that is not a multiple of the chunk,
with and without padded tokens, at decays the factorised rule overflows at;
the door's rule; that a `KimiDeltaAttention` step traced for a TPU holds both
kernels under names that carry their shape; and the layer with its rows
mapped, on the kernel path."""
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimi_linear as ref
from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.layers import KimiDeltaAttention, hybrid
from deeplearning4j_tpu.ops import delta, kda_kernels, kernel_call
from deeplearning4j_tpu.ops import pallas_kernels as pk

F32 = jnp.float32
H, D = 2, 128


def draw(rng, b, t, strongest, valid=None):
    """q, k (normalised), v, g (log decay, log-uniform down to -strongest a
    token; channel 0 a steady -strongest / 2), beta: [b, t, H, .] float32.
    Row i's tokens from valid[i] on are padding: k = 0, g = 0, beta = 0."""
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(rng.standard_normal((b, t, H, D))) * D ** -0.5
    k = unit(rng.standard_normal((b, t, H, D)))
    v = rng.standard_normal((b, t, H, D))
    g = -np.exp(rng.uniform(np.log(1e-3), np.log(strongest), (b, t, H, D)))
    g[..., 0] = -0.5 * strongest
    beta = rng.uniform(0.0, 1.0, (b, t, H))
    if valid is not None:
        keep = (np.arange(t)[None, :] < np.asarray(valid)[:, None])[..., None]
        k, g, beta = k * keep[..., None], g * keep[..., None], beta * keep
    return tuple(jnp.asarray(a, F32) for a in (q, k, v, g, beta))


def through(rule):
    """BTF arrays -> (o [b, t, H, D], the chunk-start states) by a chunk rule."""
    def f(q, k, v, g, beta):
        o, states = rule(*(hybrid.to_chunks(a) for a in (q, k, v, g, beta)))
        return hybrid.from_chunks(o, q.shape[1]), states
    return f


kernels = through(lambda *a: delta.kda_chunks(*a, impl="pallas"))
xla_form = through(hybrid.chunk_channel_gated_delta_rule)


def token_by_token(q, k, v, g, beta):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([ref.delta_recurrence(*row) for row in zip(q, k, v, g, beta)])


def with_gradients(f, ct):
    return jax.jit(lambda *a: (f(*a), jax.grad(
        lambda *a_: jnp.sum(f(*a_) * ct), tuple(range(5)))(*a)))


#: (t, the strongest per-token log decay, padded): whole chunks and a length
#: that is not; mild decays and decays whose running sum over ONE chunk falls
#: far below -100 (the existing overflow test's)
CASES = [(128, 0.3, False), (200, 0.3, False), (128, 8.0, False), (200, 8.0, True),
         (128, 0.3, True), (200, 40.0, False)]


@pytest.mark.parametrize("t,strongest,padded", CASES)
def test_kernels_are_the_xla_form_and_the_token_recurrence(t, strongest, padded, rng):
    """Outputs and all five gradients, finite and equal at any decay."""
    args = draw(rng, 2, t, strongest, valid=(t - 37, 70) if padded else None)
    if strongest >= 8.0:
        first = np.asarray(args[3])[0, :64].sum(axis=0).min()
        assert first < -100.0, first           # the factorised rule would overflow here
    ct = jnp.asarray(rng.standard_normal((2, t, H, D)), F32)
    got, g_got = with_gradients(lambda *a: kernels(*a)[0], ct)(*args)
    assert np.all(np.isfinite(got))
    for name, oracle in (("xla form", lambda *a: xla_form(*a)[0]), ("recurrence", token_by_token)):
        want, g_want = with_gradients(oracle, ct)(*args)
        np.testing.assert_allclose(got, want, atol=3e-5 * float(jnp.abs(want).max()), rtol=2e-4,
                                   err_msg=name)
        for leaf, a, b in zip(("q", "k", "v", "g", "beta"), g_got, g_want):
            assert np.all(np.isfinite(a)), leaf
            np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.abs(b).max()) + 1e-7,
                                       rtol=1e-3, err_msg=f"{name}: d{leaf}")


@pytest.mark.parametrize("t,strongest", [(200, 0.3), (192, 8.0)])
def test_chunk_start_states_are_the_xla_forms(t, strongest, rng):
    args = draw(rng, 2, t, strongest)
    (_, got), (_, want) = kernels(*args), xla_form(*args)
    assert got.shape == want.shape == (-(-t // 64), 2, H, D, D)
    assert not np.any(np.asarray(got[0]))              # every sequence starts from zero
    np.testing.assert_allclose(got, want, atol=3e-6 * float(jnp.abs(want).max()), rtol=1e-4)


@pytest.mark.parametrize("full", [False, True])
def test_the_kernels_run_at_the_policys_precision(full, rng):
    """`linear._precision()` decides, as for the XLA form's `_mm`: the
    default (one MXU pass on a TPU) or, under `dtypes.full_precision()`, the
    highest for every product."""
    args = [hybrid.to_chunks(a) for a in draw(rng, 1, 64, 0.3)]
    with mock.patch.object(kda_kernels, "kda_chunk_kernels", wraps=kda_kernels.kda_chunk_kernels) as ran:
        if full:
            with dtypes.full_precision():
                delta.kda_chunks(*args, impl="pallas")
        else:
            delta.kda_chunks(*args, impl="pallas")
    assert ran.call_args.args[5:] == (full, True)      # (highest, interpret)


def test_g_is_summed_exactly_under_the_mixed_policy(rng):
    """C g through three bfloat16 passes is float32's running sum, also at an
    exponent of -100 where ONE bf16 pass would be off by 0.4."""
    g = jnp.asarray(-rng.uniform(1.0, 3.0, (64, 128)), F32)
    cm, _ = kda_kernels._constants()
    got = kda_kernels._dot_const(jnp.asarray(cm, jnp.bfloat16), g, kda_kernels._NN, False)[:64]
    want = np.cumsum(np.asarray(g, np.float64), axis=0)
    assert want.min() < -100.0
    np.testing.assert_allclose(got, want, atol=2e-5)
    one_pass = jnp.dot(jnp.asarray(cm[:64], jnp.bfloat16), g.astype(jnp.bfloat16),
                       preferred_element_type=F32)
    assert float(jnp.abs(one_pass - want).max()) > 0.05


DOOR = [  # (impl, on tpu, shape of q, width of v, dtype, rows a device) -> which
    ("auto", True, (128, 1, 32, 64, 128), 128, F32, 1, "pallas"),
    ("auto", True, (128, 2, 32, 64, 256), 256, F32, 2, "pallas"),
    ("auto", False, (128, 1, 32, 64, 128), 128, F32, 1, "xla"),
    ("pallas", False, (2, 1, 2, 64, 128), 128, F32, 1, "pallas"),
    ("xla", True, (128, 1, 32, 64, 128), 128, F32, 1, "xla"),
    ("auto", True, (128, 1, 32, 64, 64), 64, F32, 1, "xla"),       # half a lane tile
    ("auto", True, (128, 1, 32, 64, 192), 192, F32, 1, "xla"),
    ("auto", True, (128, 1, 32, 64, 128), 256, F32, 1, "xla"),     # keys and values differ
    ("auto", True, (64, 1, 32, 128, 128), 128, F32, 1, "xla"),     # another chunk
    ("auto", True, (128, 1, 32, 64, 128), 128, jnp.bfloat16, 1, "xla"),
    ("auto", True, (128, 3, 32, 64, 128), 128, F32, 0, "xla"),     # rows do not split over the mesh
    ("pallas", True, (128, 1, 4, 64, 16), 16, F32, 1, "xla"),
]


@pytest.mark.parametrize("impl,tpu,shape,dv,dtype,rows,want", DOOR)
def test_the_door_takes_what_the_kernels_are_written_for(impl, tpu, shape, dv, dtype, rows, want,
                                                         monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu" if tpu else "cpu")
    monkeypatch.setattr(kernel_call, "per_device_batch", lambda b: rows)
    q = jax.ShapeDtypeStruct(shape, dtype)
    v = jax.ShapeDtypeStruct(shape[:-1] + (dv,), dtype)
    assert delta.kda_impl(impl, q, v) == want
    monkeypatch.setenv("DL4J_TPU_PALLAS", "0")         # the helpers' switch turns 'auto' off
    assert delta.kda_impl(impl, q, v) == (want if impl == "pallas" else "xla")


def test_a_declined_call_returns_none_and_the_layer_keeps_its_xla_form(rng):
    args = [hybrid.to_chunks(a) for a in draw(rng, 1, 64, 0.3)]
    assert delta.kda_chunks(*args) is None             # 'auto' on the CPU
    assert delta.kda_chunks(*(a[..., :16] if a.ndim == 5 else a for a in args), impl="pallas") is None


def test_under_a_data_mesh_each_device_runs_its_own_rows(rng):
    """The kernels inside ONE manual region over 'data', rows (axis 1 of the
    chunk-major arrays) split over the devices: outputs and gradients are the
    unsharded call's, and the result stays sharded by rows."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.parallel import MeshSpec, build_mesh

    if jax.device_count() < 8:
        pytest.skip("needs the 8 virtual devices of tests/conftest.py")
    args = tuple(hybrid.to_chunks(a) for a in draw(rng, 8, 70, 0.3))
    f = lambda *a: delta.kda_chunks(*a, impl="pallas")  # noqa: E731
    grads = lambda *a: jax.grad(lambda *a_: jnp.sum(f(*a_)[0] ** 2), tuple(range(5)))(*a)  # noqa: E731
    want, g_want = jax.jit(f)(*args), jax.jit(grads)(*args)
    mesh = build_mesh(MeshSpec(data=8))
    with jax.set_mesh(mesh):
        put = tuple(jax.device_put(a, NamedSharding(mesh, P(None, "data"))) for a in args)
        got, g_got = jax.jit(f)(*put), jax.jit(grads)(*put)
        assert jax.jit(f).lower(*put).as_text().count("sdy.manual_computation") == 1
    assert got[0].sharding.spec == P(None, "data")
    for a, b in zip(got + g_got, want + g_want):
        np.testing.assert_allclose(a, b, atol=1e-6)


def layer_and_input(rng, b, t):
    layer = KimiDeltaAttention(n_heads=H, head_dim=D)
    f = 32
    params = layer.init_params(jax.random.PRNGKey(3), it.recurrent(f, t))
    return layer, params, jnp.asarray(rng.standard_normal((b, t, f)), F32), it.recurrent(f, t)


def test_a_tpu_step_holds_both_kernels_by_name(rng):
    """Traced for a TPU (`jax.export`, nothing compiled), a KimiDeltaAttention
    layer's forward + backward holds `dl4j_kda_fwd` (twice: the row groups'
    checkpoint reruns it) and `dl4j_kda_bwd`, their shape in the name — the
    counter that says the mechanism engaged: a kernel chosen while the step
    is traced runs in every step or in none."""
    layer, params, x, itype = layer_and_input(rng, 2, 128)

    def loss(p, x_):
        y, _ = layer.apply(p, x_, state=layer.init_state(itype), train=True, rng=None)
        return jnp.sum(y)

    with mock.patch("jax.default_backend", return_value="tpu"), \
            mock.patch.object(KimiDeltaAttention, "CORE_BYTES", 128 * 3 * H * D * 4):
        text = jax.export.export(jax.jit(jax.grad(loss)), platforms=["tpu"])(params, x).mlir_module()
    shape = "n2_r1_h2_c64_d128_float32"
    assert text.count(f"dl4j_kda_fwd_{shape}") >= 2 and f"dl4j_kda_bwd_{shape}" in text
    # on the CPU the same layer keeps the XLA form
    cpu = jax.export.export(jax.jit(jax.grad(loss)), platforms=["cpu"])(params, x).mlir_module()
    assert "dl4j_kda" not in cpu


@pytest.mark.parametrize("part", ["fwd", "bwd"])
def test_the_benchmarks_trace_reader_folds_a_steps_calls_into_the_family(part):
    """The cell's kernels under their names as a trace holds them (one
    instruction a call site): `device_ops` adds them up under the family."""
    from benchmark import trace_reduce

    name = pk.kernel_name(f"kda_{part}", F32, **kda_kernels._names(128, 1, 32, 64, 128, 128))
    assert name == f"dl4j_kda_{part}_n128_r1_h32_c64_d128_float32"
    for site in (".41", ".43"):
        event = f"%{name}{site} = f32[128,1,32,64,128]{{4,3,2,1,0}} custom-call(f32[256,64] %a)"
        assert trace_reduce.describe(event) == f"dl4j_kda_{part}"


@pytest.mark.parametrize("masked", [False, True])
def test_kda_core_mapped_over_rows_is_the_whole_batch_on_the_kernel_path(masked, rng, monkeypatch):
    """`test_kda_mla_layers.test_kda_core_mapped_over_rows_is_the_whole_batch`
    with the kernels requested the way a TPU requests them ('auto'), run
    interpreted: rows a group at a time against all rows at once and against
    the XLA form; with a mask, the padded tokens write nothing."""
    t = 80
    layer, p, x, itype = layer_and_input(rng, 4, t)
    mask = None
    if masked:
        mask = jnp.asarray(np.arange(t)[None, :] < np.array([t, 50, 64, 7])[:, None], F32)
    run = lambda: layer.apply(p, x, state=layer.init_state(itype), train=True, rng=None, mask=mask)  # noqa: E731
    xla, st0 = run()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernel_call, "interpret", lambda: True)
    with mock.patch.object(kda_kernels, "kda_chunk_kernels", wraps=kda_kernels.kda_chunk_kernels) as ran:
        whole, st = run()
        monkeypatch.setattr(KimiDeltaAttention, "CORE_BYTES", 2 * t * 3 * H * D * 4)
        mapped, st2 = run()
    assert ran.call_count == 2
    np.testing.assert_allclose(whole, xla, atol=2e-5 * float(jnp.abs(xla).max()))
    np.testing.assert_allclose(mapped, whole, atol=2e-6)
    for k_ in ("decay_sum", "decay_min_sum", "state_max_sum"):
        assert float(st2["counters"][k_]) == pytest.approx(float(st["counters"][k_]), rel=1e-5)
        assert float(st["counters"][k_]) == pytest.approx(float(st0["counters"][k_]), rel=1e-5)
    if masked:
        assert not np.any(np.asarray(whole[1, 50:]))
