"""The scalar delta rule's Pallas kernel pair (`ops/gdn_kernels.py`,
interpreted here) behind its door `ops.delta.gdn_chunks`: outputs and every
input's gradient against the XLA form (`hybrid.chunk_gated_delta_rule`) and
against the token-by-token recurrence — at lengths that are and are not a
multiple of the chunk, strong and weak decays, one, two and four value heads
a key head, a head count a program's eight do not divide, padded tokens; the
door's rule; that a `GatedDeltaNet` step traced for a TPU holds both kernels
under names that carry their shape; and the layer with its rows mapped, on
the kernel path."""
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as ref
from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.layers import GatedDeltaNet, hybrid
from deeplearning4j_tpu.ops import chunk_kernels, delta, gdn_kernels, kernel_call
from deeplearning4j_tpu.ops import pallas_kernels as pk

F32 = jnp.float32
D = 128


def draw(rng, b, t, hk, hv, weakest, valid=None):
    """q, k [b, t, hk, D] (normalised), v [b, t, hv, D], g (log decay: the
    per-token decay exp(g) log-uniform between `weakest` and 0.9999) and beta
    [b, t, hv], float32. Row i's tokens from valid[i] on are padding: k = 0,
    g = 0, beta = 0."""
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(rng.standard_normal((b, t, hk, D))) * D ** -0.5
    k = unit(rng.standard_normal((b, t, hk, D)))
    v = rng.standard_normal((b, t, hv, D))
    g = -np.exp(rng.uniform(np.log(1e-4), np.log(-np.log(weakest)), (b, t, hv)))
    beta = rng.uniform(0.0, 1.0, (b, t, hv))
    if valid is not None:
        keep = (np.arange(t)[None, :] < np.asarray(valid)[:, None])[..., None]
        k, g, beta = k * keep[..., None], g * keep, beta * keep
    return tuple(jnp.asarray(a, F32) for a in (q, k, v, g, beta))


def through(rule):
    """BTF arrays -> o [b, t, hv, D] by a chunk rule."""
    def f(q, k, v, g, beta):
        return hybrid.from_chunks(rule(*(hybrid.to_chunks(a) for a in (q, k, v, g, beta))),
                                  q.shape[1])
    return f


kernels = through(lambda *a: delta.gdn_chunks(*a, impl="pallas"))
xla_form = through(hybrid.chunk_gated_delta_rule)


def token_by_token(q, k, v, g, beta):
    rep = v.shape[2] // q.shape[2]
    q, k = jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([ref.delta_recurrence(*row) for row in zip(q, k, v, g, beta)])


def with_gradients(f, ct):
    return jax.jit(lambda *a: (f(*a), jax.grad(
        lambda *a_: jnp.sum(f(*a_) * ct), tuple(range(5)))(*a)))


#: (t, key heads, value heads, the weakest per-token decay, padded): whole
#: chunks and a length that is not; decays near 1 and down to 0.2 a token
#: (e^-100 over a chunk); 1, 2 and 4 value heads a key head; 8 value heads (a
#: program's own count, in pairs), 6 and 3 (all heads one program, an odd
#: count unpaired), 16 (two programs a chunk)
CASES = [(128, 2, 2, 0.9, False), (200, 1, 2, 0.2, False), (70, 2, 8, 0.2, True),
         (128, 3, 6, 0.9, True), (200, 3, 3, 0.2, False), (64, 8, 16, 0.5, False)]


@pytest.mark.parametrize("t,hk,hv,weakest,padded", CASES)
def test_kernels_are_the_xla_form_and_the_token_recurrence(t, hk, hv, weakest, padded, rng):
    """Outputs and all five gradients."""
    args = draw(rng, 2, t, hk, hv, weakest, valid=(t - 37, 30) if padded else None)
    ct = jnp.asarray(rng.standard_normal((2, t, hv, D)), F32)
    got, g_got = with_gradients(kernels, ct)(*args)
    assert np.all(np.isfinite(got))
    for name, oracle in (("xla form", xla_form), ("recurrence", token_by_token)):
        want, g_want = with_gradients(oracle, ct)(*args)
        np.testing.assert_allclose(got, want, atol=3e-5 * float(jnp.abs(want).max()), rtol=2e-4,
                                   err_msg=name)
        for leaf, a, b in zip(("q", "k", "v", "g", "beta"), g_got, g_want):
            assert np.all(np.isfinite(a)), leaf
            np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.abs(b).max()) + 1e-7,
                                       rtol=1e-3, err_msg=f"{name}: d{leaf}")


def test_a_padded_token_writes_nothing_and_keeps_the_state(rng):
    """Past a row's valid length k = 0, g = 0, beta = 0: the outputs of the
    tokens before are those of the row cut there, and no gradient reaches
    what the padding holds."""
    t, cut = 130, 70
    args = draw(rng, 1, t, 2, 4, 0.5, valid=(cut,))
    got = kernels(*args)
    short = kernels(*(a[:, :cut] for a in args))
    np.testing.assert_allclose(got[:, :cut], short, atol=1e-6)
    dv = jax.grad(lambda v: jnp.sum(kernels(args[0], args[1], v, *args[3:]) ** 2))(args[2])
    assert not np.any(np.asarray(dv[:, cut:]))


@pytest.mark.parametrize("full", [False, True])
def test_the_kernels_run_at_the_policys_precision(full, rng):
    """`linear._precision()` decides, as for the XLA form's `_mm`: the
    default (one MXU pass on a TPU) or, under `dtypes.full_precision()`, the
    highest for every product."""
    args = [hybrid.to_chunks(a) for a in draw(rng, 1, 64, 1, 2, 0.9)]
    with mock.patch.object(gdn_kernels, "gdn_chunk_kernels", wraps=gdn_kernels.gdn_chunk_kernels) as ran:
        if full:
            with dtypes.full_precision():
                delta.gdn_chunks(*args, impl="pallas")
        else:
            delta.gdn_chunks(*args, impl="pallas")
    assert ran.call_args.args[5:] == (full, True)      # (highest, interpret)


def test_g_is_summed_exactly_under_the_mixed_policy(rng):
    """g [heads, c] x the triangle through three bfloat16 passes is float32's
    running sum along each row, also at an exponent of -100 where ONE bf16
    pass would be off by 0.4; run backwards it is the sum from the end."""
    g = jnp.asarray(-rng.uniform(1.0, 3.0, (8, 64)), F32)
    tri = jnp.asarray(chunk_kernels._pairs()[0], jnp.bfloat16)
    got = gdn_kernels._running_sums(g, tri, False)
    want = np.cumsum(np.asarray(g, np.float64), axis=1)
    assert want.min() < -100.0
    np.testing.assert_allclose(got, want, atol=2e-5)
    one_pass = jnp.dot(g.astype(jnp.bfloat16), tri.T, preferred_element_type=F32)
    assert float(jnp.abs(one_pass - want).max()) > 0.05
    back = chunk_kernels._dot_const(tri, g, chunk_kernels._NN, False, const_first=False)
    np.testing.assert_allclose(back, np.cumsum(np.asarray(g, np.float64)[:, ::-1], axis=1)[:, ::-1],
                               atol=2e-5)


DOOR = [  # (impl, on tpu, shape of q, value heads, width of v, dtype, rows a device) -> which
    ("auto", True, (128, 1, 16, 64, 128), 32, 128, F32, 1, "pallas"),
    ("auto", True, (128, 2, 4, 64, 256), 4, 256, F32, 2, "pallas"),
    ("auto", True, (128, 1, 8, 64, 128), 24, 128, F32, 1, "pallas"),   # 3 a key head: all heads a program
    ("auto", False, (128, 1, 16, 64, 128), 32, 128, F32, 1, "xla"),
    ("pallas", False, (2, 1, 1, 64, 128), 2, 128, F32, 1, "pallas"),
    ("xla", True, (128, 1, 16, 64, 128), 32, 128, F32, 1, "xla"),
    ("auto", True, (128, 1, 16, 64, 64), 32, 64, F32, 1, "xla"),       # half a lane tile
    ("auto", True, (128, 1, 16, 64, 128), 32, 256, F32, 1, "xla"),     # keys and values differ
    ("auto", True, (64, 1, 16, 128, 128), 32, 128, F32, 1, "xla"),     # another chunk
    ("auto", True, (128, 1, 16, 64, 128), 32, 128, jnp.bfloat16, 1, "xla"),
    ("auto", True, (128, 1, 16, 64, 128), 24, 128, F32, 1, "xla"),     # 16 does not divide 24
    ("auto", True, (128, 3, 16, 64, 128), 32, 128, F32, 0, "xla"),     # rows do not split over the mesh
    ("pallas", True, (128, 1, 2, 64, 8), 4, 8, F32, 1, "xla"),
]


@pytest.mark.parametrize("impl,tpu,shape,hv,dv,dtype,rows,want", DOOR)
def test_the_door_takes_what_the_kernels_are_written_for(impl, tpu, shape, hv, dv, dtype, rows, want,
                                                         monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu" if tpu else "cpu")
    monkeypatch.setattr(kernel_call, "per_device_batch", lambda b: rows)
    q = jax.ShapeDtypeStruct(shape, dtype)
    v = jax.ShapeDtypeStruct(shape[:2] + (hv, shape[3], dv), dtype)
    assert delta.gdn_impl(impl, q, v) == want
    monkeypatch.setenv("DL4J_TPU_PALLAS", "0")         # the helpers' switch turns 'auto' off
    assert delta.gdn_impl(impl, q, v) == (want if impl == "pallas" else "xla")


@pytest.mark.parametrize("hk,hv,heads,keys", [(16, 32, 8, 4), (4, 4, 4, 4), (8, 24, 24, 8), (2, 16, 8, 1)])
def test_a_program_takes_the_key_heads_its_value_heads_read(hk, hv, heads, keys):
    """Eight value heads a program and the key heads they read — all heads
    where eight do not divide hv or hold no whole number of key heads."""
    q = jax.ShapeDtypeStruct((4, 1, hk, 64, 128), F32)
    v = jax.ShapeDtypeStruct((4, 1, hv, 64, 128), F32)
    p = gdn_kernels._calls(q, v, reverse=False)
    assert (p.grid, p.heads) == ((1, hv // heads, 4), heads)
    assert p.tokens(128).block_shape[2] == heads and p.keys(128).block_shape[2] == keys


def test_a_declined_call_returns_none_and_the_layer_keeps_its_xla_form(rng):
    args = [hybrid.to_chunks(a) for a in draw(rng, 1, 64, 1, 2, 0.9)]
    assert delta.gdn_chunks(*args) is None             # 'auto' on the CPU
    assert delta.gdn_chunks(*(a[..., :16] if a.ndim == 5 else a for a in args), impl="pallas") is None
    layer, params, x, itype = layer_and_input(rng, 2, 64)
    with mock.patch.object(gdn_kernels, "gdn_chunk_kernels", wraps=gdn_kernels.gdn_chunk_kernels) as ran, \
            mock.patch.object(hybrid, "chunk_gated_delta_rule", wraps=hybrid.chunk_gated_delta_rule) as xla:
        layer.apply(params, x, state={}, train=True, rng=None)
    assert ran.call_count == 0 and xla.call_count == 1


def test_under_a_data_mesh_each_device_runs_its_own_rows(rng):
    """The kernels inside ONE manual region over 'data', rows (axis 1 of the
    chunk-major arrays) split over the devices: outputs and gradients are the
    unsharded call's, and the result stays sharded by rows."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.parallel import MeshSpec, build_mesh

    if jax.device_count() < 8:
        pytest.skip("needs the 8 virtual devices of tests/conftest.py")
    args = tuple(hybrid.to_chunks(a) for a in draw(rng, 8, 70, 1, 2, 0.5))
    f = lambda *a: delta.gdn_chunks(*a, impl="pallas")  # noqa: E731
    grads = lambda *a: jax.grad(lambda *a_: jnp.sum(f(*a_) ** 2), tuple(range(5)))(*a)  # noqa: E731
    want, g_want = jax.jit(f)(*args), jax.jit(grads)(*args)
    mesh = build_mesh(MeshSpec(data=8))
    with jax.set_mesh(mesh):
        put = tuple(jax.device_put(a, NamedSharding(mesh, P(None, "data"))) for a in args)
        got, g_got = jax.jit(f)(*put), jax.jit(grads)(*put)
        assert jax.jit(f).lower(*put).as_text().count("sdy.manual_computation") == 1
    assert got.sharding.spec == P(None, "data")
    for a, b in zip((got,) + g_got, (want,) + g_want):
        np.testing.assert_allclose(a, b, atol=1e-6)


def layer_and_input(rng, b, t, hk=1, hv=2):
    layer = GatedDeltaNet(n_key_heads=hk, n_value_heads=hv, key_dim=D, value_dim=D)
    f = 32
    params = layer.init_params(jax.random.PRNGKey(3), it.recurrent(f, t))
    return layer, params, jnp.asarray(rng.standard_normal((b, t, f)), F32), it.recurrent(f, t)


def test_a_tpu_step_holds_both_kernels_by_name(rng):
    """Traced for a TPU (`jax.export`, nothing compiled), a GatedDeltaNet
    layer's forward + backward holds `dl4j_gdn_fwd` (twice: the row groups'
    checkpoint reruns it) and `dl4j_gdn_bwd`, their shape in the name — the
    counter that says the mechanism engaged: a kernel chosen while the step
    is traced runs in every step or in none."""
    layer, params, x, _ = layer_and_input(rng, 2, 128)

    def loss(p, x_):
        y, _ = layer.apply(p, x_, state={}, train=True, rng=None)
        return jnp.sum(y)

    with mock.patch("jax.default_backend", return_value="tpu"), \
            mock.patch.object(GatedDeltaNet, "CORE_BYTES", 128 * 4 * D * 4):
        text = jax.export.export(jax.jit(jax.grad(loss)), platforms=["tpu"])(params, x).mlir_module()
    shape = "n2_r1_h2k1_c64_d128_float32"
    assert text.count(f"dl4j_gdn_fwd_{shape}") >= 2 and f"dl4j_gdn_bwd_{shape}" in text
    # on the CPU the same layer keeps the XLA form
    cpu = jax.export.export(jax.jit(jax.grad(loss)), platforms=["cpu"])(params, x).mlir_module()
    assert "dl4j_gdn" not in cpu


@pytest.mark.parametrize("part", ["fwd", "bwd"])
def test_the_benchmarks_trace_reader_folds_a_steps_calls_into_the_family(part):
    """The cell's kernels under their names as a trace holds them (one
    instruction a call site): `device_ops` adds them up under the family."""
    from benchmark import trace_reduce

    name = pk.kernel_name(f"gdn_{part}", F32, **chunk_kernels._names(128, 1, "32k16", 64, 128, 128))
    assert name == f"dl4j_gdn_{part}_n128_r1_h32k16_c64_d128_float32"
    for site in (".41", ".43"):
        event = f"%{name}{site} = f32[128,1,32,64,128]{{4,3,2,1,0}} custom-call(f32[64,64] %a)"
        assert trace_reduce.describe(event) == f"dl4j_gdn_{part}"


@pytest.mark.parametrize("masked", [False, True])
def test_delta_core_mapped_over_rows_is_the_whole_batch_on_the_kernel_path(masked, rng, monkeypatch):
    """`test_hybrid_layers.test_delta_core_mapped_over_rows_is_the_whole_batch`
    with the kernels requested the way a TPU requests them ('auto'), run
    interpreted: rows a group at a time against all rows at once and against
    the XLA form, values and the gradients of the parameters and the input;
    with a mask, the padded tokens write nothing."""
    t = 80
    layer, p, x, _ = layer_and_input(rng, 4, t, hk=2, hv=4)
    mask = None
    if masked:
        mask = jnp.asarray(np.arange(t)[None, :] < np.array([t, 50, 64, 7])[:, None], F32)

    def run():
        def loss(p_, x_):
            y, _ = layer.apply(p_, x_, state={}, train=True, rng=None, mask=mask)
            return jnp.sum(y * y), y
        return jax.tree_util.tree_leaves(jax.value_and_grad(loss, (0, 1), has_aux=True)(p, x))

    xla = run()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernel_call, "interpret", lambda: True)
    with mock.patch.object(gdn_kernels, "gdn_chunk_kernels", wraps=gdn_kernels.gdn_chunk_kernels) as ran:
        whole = run()
        monkeypatch.setattr(GatedDeltaNet, "CORE_BYTES", 2 * t * 8 * D * 4)
        mapped = run()
    assert ran.call_count == 2
    for a, b, c in zip(whole, xla, mapped):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.abs(b).max()) + 1e-8)
        np.testing.assert_allclose(c, a, atol=1e-5 * float(jnp.abs(a).max()) + 1e-8)
    if masked:
        assert not np.any(np.asarray(whole[1][1, 50:]))
