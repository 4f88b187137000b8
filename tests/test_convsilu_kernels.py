"""The short convolution + silu's Pallas kernel pair (`ops/convsilu_kernels.py`,
interpreted here) behind its door `ops.delta.conv_silu_chunks`: the result
and the gradients of x, w and b against the XLA form (`hybrid._conv_silu`) and
against a plain zero-padded convolution over the tokens — both layouts of a
head's chunk (tokens on the sublanes at d 128, on the lanes at d 64), bfloat16
and float32 rows, one chunk and several, a length that is no multiple of the
chunk, one row and two, one program and many; the door's rule; that each of
the three recurrent mixers' steps traced for a TPU holds both kernels under
names that carry their shape; and the row mapping under a data mesh."""
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.layers import GatedDeltaNet, KimiDeltaAttention, Mamba2Mixer, hybrid
from deeplearning4j_tpu.ops import convsilu_kernels, delta, kernel_call
from deeplearning4j_tpu.ops import pallas_kernels as pk

F32, BF16 = jnp.float32, jnp.bfloat16
CW = 4


def draw(rng, b, t, h, d, bias, dtype):
    """x [b, t, h, d] in `dtype`, taps [CW, h d], bias [h d] or None, and a
    cotangent [b, t, h, d], float32."""
    x = jnp.asarray(rng.standard_normal((b, t, h, d)), F32).astype(dtype)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (CW, h * d)), F32)
    bb = jnp.asarray(0.5 * rng.standard_normal((h * d,)), F32) if bias else None
    return x, w, bb, jnp.asarray(rng.standard_normal((b, t, h, d)), F32)


def chunked(conv, c):
    """Token-major arrays -> [b, t, h, d] float32 through a chunk-major `conv`."""
    def f(x, w, bb):
        h, d = x.shape[2:]
        y = conv(hybrid.to_chunks(x, c), w.reshape(CW, h, 1, d),
                 None if bb is None else bb.reshape(h, 1, d))
        return hybrid.from_chunks(y, x.shape[1])
    return f


def kernels(c):
    return chunked(lambda *a: delta.conv_silu_chunks(*a, impl="pallas"), c)


def plain(x, w, bb):
    """silu(sum_j w_j x_{i - (CW - 1) + j} + b), zeros before the first token."""
    b, t, h, d = x.shape
    padded = jnp.pad(x.astype(F32).reshape(b, t, h * d), ((0, 0), (CW - 1, 0), (0, 0)))
    pre = sum(padded[:, j:j + t] * w[j] for j in range(CW))
    return jax.nn.silu(pre if bb is None else pre + bb).reshape(b, t, h, d)


def with_gradients(f, ct, bias):
    return jax.jit(lambda *a: (f(*a), jax.grad(
        lambda *a_: jnp.sum(f(*a_) * ct), (0, 1, 2) if bias else (0, 1))(*a)))


#: (chunk, head width, bias): the delta rules' operands, Mamba-2's 64-wide
#: heads (the lanes form), its B | C groups
SHAPES = [(64, 128, False), (128, 64, True), (128, 128, True)]
#: (rows, tokens in chunks): one chunk; several; a length that is no multiple
ROWS = [(1, 1.0), (2, 3.0), (2, 2.3)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("b,chunks", ROWS)
@pytest.mark.parametrize("c,d,bias", SHAPES)
def test_kernels_are_the_xla_form_and_the_plain_convolution(c, d, bias, b, chunks, dtype, rng):
    """y, dx, dw and db; a bf16 x takes its cotangent rounded once."""
    t, h = int(chunks * c), 3
    x, w, bb, ct = draw(rng, b, t, h, d, bias, dtype)
    got, g_got = with_gradients(kernels(c), ct, bias)(x, w, bb)
    assert got.dtype == F32 and g_got[0].dtype == x.dtype and np.all(np.isfinite(got))
    for name, oracle in (("xla form", chunked(hybrid._conv_silu, c)), ("plain", plain)):
        want, g_want = with_gradients(oracle, ct, bias)(
            x if name == "xla form" else x.astype(F32), w, bb)
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-5, err_msg=name)
        one_rounding = 2.0 ** -7 if dtype == "bfloat16" else 1e-5   # a unit in bf16's last place
        np.testing.assert_allclose(g_got[0].astype(F32), g_want[0].astype(F32), rtol=one_rounding,
                                   atol=1e-6 * float(jnp.abs(g_want[0].astype(F32)).max()),
                                   err_msg=f"{name}: dx")
        for leaf, u, v in zip(("dw", "db"), g_got[1:], g_want[1:]):
            assert u.dtype == F32 and u.shape == v.shape
            np.testing.assert_allclose(u, v, atol=1e-5 * float(jnp.abs(v).max()), rtol=1e-4,
                                       err_msg=f"{name}: {leaf}")


@pytest.mark.parametrize("c,d,bias", SHAPES)
def test_many_programs_are_one(c, d, bias, rng, monkeypatch):
    """A block of ONE head's chunk a program — every halo crosses a block's
    border, dw and db add up over rows, chunk blocks and head blocks — gives
    what one program a row gives."""
    x, w, bb, ct = draw(rng, 2, 4 * c, 4, d, bias, "bfloat16")
    whole = with_gradients(kernels(c), ct, bias)(x, w, bb)
    assert convsilu_kernels._plan(4, 4, c, d)[1] == 4
    monkeypatch.setattr(convsilu_kernels, "_BLOCK", 2 * c * d)
    assert convsilu_kernels._plan(4, 4, c, d) == (2, 1)
    split = with_gradients(kernels(c), ct, bias)(x, w, bb)
    for a, b_ in zip(jax.tree_util.tree_leaves(whole), jax.tree_util.tree_leaves(split)):
        np.testing.assert_allclose(a.astype(F32), b_.astype(F32), rtol=2.0 ** -7 if a.dtype == BF16 else 0,
                                   atol=2e-6 * float(jnp.abs(a.astype(F32)).max()))


def test_dx_rounds_once(rng):
    """The cw taps' terms add up in float32 and round to bfloat16 ONCE: all
    but a few of the kernels' dx are the float32 gradient's nearest bfloat16
    (the few: float32 sums taken in another order, a unit in the last place
    apart), where a sum of cw rounded terms would be off in a third."""
    c, d = 64, 128
    x, w, bb, ct = draw(rng, 1, 3 * c, 2, d, False, "bfloat16")
    dx = jax.grad(lambda x_: jnp.sum(kernels(c)(x_, w, bb) * ct))(x)
    exact = jax.grad(lambda x_: jnp.sum(plain(x_, w, bb) * ct))(x.astype(F32))
    assert dx.dtype == BF16
    same = np.asarray(dx == exact.astype(BF16))
    assert same.mean() > 0.999
    np.testing.assert_allclose(dx.astype(F32), exact, rtol=2.0 ** -7)


DOOR = [  # (impl, on tpu, shape of x, taps, dtype, rows a device) -> which
    ("auto", True, (128, 1, 32, 64, 128), 4, BF16, 1, "pallas"),       # Qwen3-Next's q | k and v
    ("auto", True, (128, 1, 96, 64, 128), 4, BF16, 1, "pallas"),       # Kimi-Linear's q | k | v
    ("auto", True, (64, 1, 64, 128, 64), 4, BF16, 1, "pallas"),        # Nemotron's x: the lanes form
    ("auto", True, (64, 1, 16, 128, 128), 4, BF16, 1, "pallas"),       # Nemotron's B | C
    ("auto", True, (64, 2, 16, 128, 256), 4, F32, 2, "pallas"),
    ("auto", False, (128, 1, 32, 64, 128), 4, BF16, 1, "xla"),
    ("pallas", False, (2, 1, 2, 64, 128), 4, F32, 1, "pallas"),
    ("xla", True, (128, 1, 32, 64, 128), 4, BF16, 1, "xla"),
    ("auto", True, (128, 1, 32, 64, 64), 4, BF16, 1, "xla"),           # half a lane tile over half-tile chunks
    ("auto", True, (128, 1, 32, 128, 8), 4, BF16, 1, "xla"),           # half a packed sublane tile
    ("auto", True, (128, 1, 32, 72, 128), 4, BF16, 1, "xla"),          # a chunk of broken tiles
    ("auto", True, (128, 1, 32, 64, 192), 4, BF16, 1, "xla"),          # one and a half lane tiles
    ("auto", True, (128, 1, 32, 64, 128), 4, jnp.float16, 1, "xla"),
    ("auto", True, (128, 1, 32, 64, 128), 12, BF16, 1, "xla"),         # taps beyond the 8 rows kept
    ("auto", True, (128, 3, 32, 64, 128), 4, BF16, 0, "xla"),          # rows do not split over the mesh
    ("pallas", True, (128, 1, 3, 32, 8), 4, F32, 1, "xla"),            # the existing tests' sizes
]


@pytest.mark.parametrize("impl,tpu,shape,cw,dtype,rows,want", DOOR)
def test_the_door_takes_what_the_kernels_are_written_for(impl, tpu, shape, cw, dtype, rows, want,
                                                         monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu" if tpu else "cpu")
    monkeypatch.setattr(kernel_call, "per_device_batch", lambda b: rows)
    x = jax.ShapeDtypeStruct(shape, dtype)
    w = jax.ShapeDtypeStruct((cw, shape[2], 1, shape[4]), F32)
    assert delta.conv_silu_impl(impl, x, w) == want
    monkeypatch.setenv("DL4J_TPU_PALLAS", "0")         # the helpers' switch turns 'auto' off
    assert delta.conv_silu_impl(impl, x, w) == (want if impl == "pallas" else "xla")


@pytest.mark.parametrize("n,h,c,d,want", [
    (128, 32, 64, 128, (32, 4)), (128, 96, 64, 128, (96, 1)), (64, 16, 128, 128, (16, 4)),
    (64, 64, 128, 64, (8, 16)),      # the lanes form: its halo is a whole chunk, so few heads, many chunks
    (3, 5, 64, 128, (5, 3)), (128, 1, 64, 128, (1, 128))])
def test_a_program_takes_whole_heads_chunks_up_to_a_block(n, h, c, d, want):
    assert convsilu_kernels._plan(n, h, c, d) == want
    hb, nb = want
    assert h % hb == 0 and n % nb == 0 and hb * nb * c * d <= convsilu_kernels._BLOCK


def test_a_declined_call_returns_none_and_the_layer_keeps_its_xla_form(rng):
    x = jnp.asarray(rng.standard_normal((2, 1, 2, 64, 128)), BF16)
    w = jnp.asarray(rng.standard_normal((CW, 2, 1, 128)), F32)
    assert delta.conv_silu_chunks(x, w) is None                     # 'auto' on the CPU
    assert delta.conv_silu_chunks(x[..., :8], w[..., :8], impl="pallas") is None
    with mock.patch.object(convsilu_kernels, "conv_silu_kernels") as ran, \
            mock.patch.object(hybrid, "_conv_silu", wraps=hybrid._conv_silu) as xla:
        hybrid.conv_silu(x, w)
    assert ran.call_count == 0 and xla.call_count == 1


def test_under_a_data_mesh_each_device_runs_its_own_rows(rng):
    """The kernels inside ONE manual region over 'data', rows (axis 1 of the
    chunk-major x) split over the devices, w and b whole: the result stays
    sharded by rows, dx is the unsharded call's and dw, db are summed over
    the devices."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.parallel import MeshSpec, build_mesh

    if jax.device_count() < 8:
        pytest.skip("needs the 8 virtual devices of tests/conftest.py")
    x = jnp.asarray(rng.standard_normal((3, 8, 2, 128, 64)), BF16)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (CW, 2, 1, 64)), F32)
    bb = jnp.asarray(rng.standard_normal((2, 1, 64)), F32)
    f = lambda *a: delta.conv_silu_chunks(*a, impl="pallas")  # noqa: E731
    grads = lambda *a: jax.grad(lambda *a_: jnp.sum(f(*a_) ** 2), (0, 1, 2))(*a)  # noqa: E731
    want, g_want = jax.jit(f)(x, w, bb), jax.jit(grads)(x, w, bb)
    mesh = build_mesh(MeshSpec(data=8))
    with jax.set_mesh(mesh):
        put = (jax.device_put(x, NamedSharding(mesh, P(None, "data"))), w, bb)
        got, g_got = jax.jit(f)(*put), jax.jit(grads)(*put)
        assert jax.jit(f).lower(*put).as_text().count("sdy.manual_computation") == 1
    assert got.sharding.spec == P(None, "data")
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_array_equal(g_got[0], g_want[0])
    for a, b_ in zip(g_got[1:], g_want[1:]):
        np.testing.assert_allclose(a, b_, atol=1e-5 * float(jnp.abs(b_).max()))


def mixer(kind):
    """(a small layer of `kind` whose convolution operands the door admits,
    the kernel names' shape part(s) at t 256 with its rows mapped one at a time)."""
    if kind == "gdn":
        return (GatedDeltaNet(n_key_heads=1, n_value_heads=2, key_dim=128, value_dim=128),
                ["n4_r1_h2_c64_d128_float32"])
    if kind == "kda":
        return KimiDeltaAttention(n_heads=2, head_dim=128), ["n4_r1_h6_c64_d128_float32"]
    return (Mamba2Mixer(n_heads=4, head_dim=64, n_groups=1, state_dim=128, chunk=128),
            ["n2_r1_h4_c128_d64_float32", "n2_r1_h2_c128_d128_float32"])


@pytest.mark.parametrize("kind", ["gdn", "kda", "mamba2"])
def test_a_tpu_step_of_each_mixer_holds_both_kernels_by_name(kind, rng):
    """Traced for a TPU (`jax.export`, nothing compiled), a recurrent mixer's
    forward + backward holds `dl4j_convsilu_fwd` (twice a call site: the row
    groups' checkpoint reruns it) and `dl4j_convsilu_bwd`, their shape in the
    name: a kernel chosen while the step is traced runs in every step or in
    none. (That they sit under the `conv` part: tests/test_device_scopes.py.)"""
    layer, shapes = mixer(kind)
    t, f = 256, 32
    itype = it.recurrent(f, t)
    params = layer.init_params(jax.random.PRNGKey(3), itype)
    x = jnp.asarray(rng.standard_normal((2, t, f)), F32)

    def loss(p, x_):
        y, _ = layer.apply(p, x_, state=layer.init_state(itype), train=True, rng=None)
        return jnp.sum(y)

    with mock.patch("jax.default_backend", return_value="tpu"), \
            mock.patch.object(type(layer), "CORE_BYTES", 1):
        text = jax.export.export(jax.jit(jax.grad(loss)), platforms=["tpu"])(params, x).mlir_module()
    for shape in shapes:
        assert text.count(f"dl4j_convsilu_fwd_{shape}") >= 2, shape
        assert f"dl4j_convsilu_bwd_{shape}" in text, shape
    # on the CPU the same layer keeps the XLA form
    cpu = jax.export.export(jax.jit(jax.grad(loss)), platforms=["cpu"])(params, x).mlir_module()
    assert "dl4j_convsilu" not in cpu


@pytest.mark.parametrize("part", ["fwd", "bwd"])
@pytest.mark.parametrize("shape,dims", [
    ("n128_r1_h96_c64_d128_bfloat16", (128, 1, 96, 64, 128)),
    ("n64_r1_h64_c128_d64_bfloat16", (64, 1, 64, 128, 64))])
def test_the_benchmarks_trace_reader_folds_a_steps_calls_into_the_family(part, shape, dims):
    """The cells' kernels under their names as a trace holds them (one
    instruction a call site): `device_ops` adds them up under the family."""
    from benchmark import trace_reduce

    n, r, h, c, d = dims
    name = pk.kernel_name(f"convsilu_{part}", BF16, n=n, r=r, h=h, c=c, d=d)
    assert name == f"dl4j_convsilu_{part}_{shape}"
    for site in (".64", ".48"):
        event = f"%{name}{site} = f32[{n},{r},{h},{c},{d}]{{4,3,2,1,0}} custom-call(bf16[{n},{r},{h},{c},{d}] %a)"
        assert trace_reduce.describe(event) == f"dl4j_convsilu_{part}"


@pytest.mark.parametrize("kind", ["gdn", "mamba2"])
def test_a_mixer_on_the_kernel_path_is_the_mixer_on_the_xla_form(kind, rng, monkeypatch):
    """The layer with the kernels requested the way a TPU requests them
    ('auto'), run interpreted, rows a group at a time: values and the
    gradients of the parameters and the input are the XLA form's."""
    layer, _ = mixer(kind)
    t, f = 200, 32
    itype = it.recurrent(f, t)
    p = layer.init_params(jax.random.PRNGKey(3), itype)
    x = jnp.asarray(rng.standard_normal((2, t, f)), F32)

    def run():
        def loss(p_, x_):
            y, _ = layer.apply(p_, x_, state=layer.init_state(itype), train=True, rng=None)
            return jnp.sum(y * y), y
        return jax.tree_util.tree_leaves(jax.value_and_grad(loss, (0, 1), has_aux=True)(p, x))

    xla = run()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernel_call, "interpret", lambda: True)
    monkeypatch.setattr(type(layer), "CORE_BYTES", 1)
    with mock.patch.object(convsilu_kernels, "conv_silu_kernels",
                           wraps=convsilu_kernels.conv_silu_kernels) as ran:
        got = run()
    assert ran.call_count == 2
    for a, b_ in zip(got, xla):
        np.testing.assert_allclose(a, b_, atol=1e-4 * float(jnp.abs(b_).max()) + 1e-8)
