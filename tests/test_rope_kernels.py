"""The rotation + head split's Pallas kernel pair (`ops/rope_kernels.py`,
interpreted here) behind its door `ops.attention.rope_heads`: the result
against the sliced half-split rotation `hybrid.rotary` was until PR 46 (kept
here as `before`) behind the transpose to heads, to one rounding, bfloat16
and float32, at every shape of part the door admits; its gradient against the
XLA form's; rot(angle) then rot(-angle) is the identity; the layout read from
a [q | k | v] and a [q | g | k | v] projection; the door's rule; that
`GatedAttention` reaches the kernels exactly where its projection needs no
norm and no gate in front, with the XLA path's output and gradients; the step
traced for a TPU holds both kernels under names the trace reader folds; and
the row mapping under a data mesh."""
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.layers import GatedAttention, hybrid
from deeplearning4j_tpu.ops import attention as att
from deeplearning4j_tpu.ops import kernel_call, rope_kernels
from deeplearning4j_tpu.ops import pallas_kernels as pk

F32, BF16 = jnp.float32, jnp.bfloat16
THETA = 1e4

#: (heads a part, which parts turn, head width, features turned)
PARTS = [
    ((2, 1, 1), (True, True, False), 128, 128),     # Ouro's [q | k | v]: the whole 128-lane head
    ((2,), (True,), 256, 64),                       # Qwen3-Next's head: 64 of 256
    ((1, 1), (True, False), 256, 256),              # a whole head of two lane tiles: the tiles change places
    ((3,), (True,), 128, 32),                       # a part narrower than half a tile
    ((1, 2), (False, True), 384, 128),              # one whole tile of three
]


def before(x, rotary_dim, theta):
    """`hybrid.rotary`'s half-split form until PR 46: the part's halves sliced
    out, turned and concatenated back, float32 inside."""
    t, half = x.shape[2], rotary_dim // 2
    j = jnp.arange(half, dtype=F32)
    ang = jnp.arange(t, dtype=F32)[:, None] * theta ** (-2.0 * j / rotary_dim)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(F32)
    a, b, rest = xf[..., :half], xf[..., half:rotary_dim], xf[..., rotary_dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1).astype(x.dtype)


def split_then(rotation, a, heads, turned, d, rot):
    """The parts of a [b, t, sum(heads) d] as [b, n, t, d], a turned part
    through `rotation`: what the layer did in XLA."""
    b, t, _ = a.shape
    out, col = [], 0
    for n, turn in zip(heads, turned):
        x = a[..., col:col + n * d].reshape(b, t, n, d).transpose(0, 2, 1, 3)
        out.append(rotation(x, rot, THETA) if turn else x)
        col += n * d
    return tuple(out)


def kernels(a, heads, turned, d, rot):
    return att.rope_heads(a, heads, turned, d, rot, THETA, impl="pallas")


def one_rounding(got, want, dtype):
    """Equal but for the last bit `dtype` gives the largest element (the two
    forms multiply and add in float32 in another order, then round once)."""
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=0,
                               atol=float(jnp.finfo(dtype).eps) * float(np.abs(want).max()))


@pytest.mark.parametrize("dtype", [BF16, F32])
@pytest.mark.parametrize("heads,turned,d,rot", PARTS)
def test_kernel_is_the_head_split_and_the_sliced_rotation(heads, turned, d, rot, dtype, rng):
    a = jnp.asarray(rng.standard_normal((2, 48, sum(heads) * d)), F32).astype(dtype)
    got = kernels(a, heads, turned, d, rot)
    want = split_then(before, a, heads, turned, d, rot)
    assert len(got) == len(heads)
    for g, w, n in zip(got, want, heads):
        assert g.shape == (2, n, 48, d) and g.dtype == dtype
        one_rounding(g, w, dtype)
    assert float(jnp.abs(got[turned.index(True)].astype(F32)[:, :, 1:]
                         - want[turned.index(True)].astype(F32)[:, :, :1]).max()) > 0.1   # it turns


@pytest.mark.parametrize("dtype", [BF16, F32])
@pytest.mark.parametrize("heads,turned,d,rot", PARTS)
def test_gradient_is_the_xla_forms(heads, turned, d, rot, dtype, rng):
    """`jax.grad` through the backward kernel against autodiff through
    `hybrid.rotary` behind the transposes, under the same cotangents."""
    a = jnp.asarray(rng.standard_normal((2, 32, sum(heads) * d)), F32).astype(dtype)
    cts = [jnp.asarray(rng.standard_normal((2, n, 32, d)), F32) for n in heads]

    def scored(f):
        return jax.grad(lambda a_: sum(jnp.sum(y.astype(F32) * ct) for y, ct in zip(f(a_), cts)))(a)

    got = scored(lambda a_: kernels(a_, heads, turned, d, rot))
    want = scored(lambda a_: split_then(hybrid.rotary, a_, heads, turned, d, rot))
    assert got.shape == a.shape and got.dtype == dtype
    eps = float(jnp.finfo(dtype).eps)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=4 * eps * float(jnp.abs(want.astype(F32)).max()))


@pytest.mark.parametrize("heads,turned,d,rot", PARTS)
def test_turning_back_is_the_identity(heads, turned, d, rot, rng):
    """A rotation is orthogonal and the head split a permutation: the
    backward kernel (sin negated, heads -> columns) undoes the forward one."""
    a = jnp.asarray(rng.standard_normal((1, 64, sum(heads) * d)), F32)
    out, back = jax.vjp(lambda a_: kernels(a_, heads, turned, d, rot), a)
    np.testing.assert_allclose(back(out)[0], a, atol=2e-6)
    assert float(jnp.abs(jnp.concatenate([o.transpose(0, 2, 1, 3).reshape(1, 64, -1) for o in out], -1)
                         - a).max()) > 0.1


@pytest.mark.parametrize("gated", [False, True])
def test_layout_is_heads_of_the_projections_columns(gated, rng):
    """[q | k | v] and [q | g | k | v]: part p is `heads()` of its own columns,
    whatever stands between them."""
    h, kv, d = 4, 2, 128
    heads = (h, h, kv, kv) if gated else (h, kv, kv)
    turned = (True, False, True, False) if gated else (True, True, False)
    z = jnp.asarray(rng.standard_normal((2, 32, sum(heads) * d)), BF16)
    got = kernels(z, heads, turned, d, d)
    cols = jnp.split(z, list(np.cumsum([n * d for n in heads])[:-1]), axis=-1)
    for g, c, n, turn in zip(got, cols, heads, turned):
        plain = c.reshape(2, 32, n, d).transpose(0, 2, 1, 3)
        if turn:
            one_rounding(g, before(plain, d, THETA), BF16)
        else:
            np.testing.assert_array_equal(g, plain)


DOOR = [
    # impl, on a TPU, t, d, rot, dtype, rows a device, -> what runs
    ("auto", True, 8192, 128, 128, BF16, 1, "pallas"),                 # Ouro
    ("auto", True, 8192, 256, 64, BF16, 2, "pallas"),                  # Qwen3-Next's head (its layer has norms in front)
    ("auto", True, 8192, 256, 256, F32, 1, "pallas"),
    ("auto", True, 8192, 64, 64, BF16, 2, "xla"),                      # LFM2: half a lane tile a head
    ("auto", True, 8191, 128, 128, BF16, 1, "xla"),                    # tokens that are no whole tiles
    ("auto", True, 8192, 256, 192, BF16, 1, "xla"),                    # a part across one and a half tiles
    ("auto", True, 8192, 128, 0, BF16, 1, "xla"),
    ("auto", True, 8192, 128, 128, jnp.float16, 1, "xla"),
    ("auto", True, 8192, 128, 128, BF16, 0, "xla"),                    # a model-sharded mesh, or rows that do not split
    ("auto", False, 8192, 128, 128, BF16, 1, "xla"),                   # the CPU keeps the XLA form
    ("pallas", False, 48, 128, 128, BF16, 0, "pallas"),                # the tests' way in
    ("pallas", False, 48, 64, 64, BF16, 1, "xla"),
    ("xla", True, 8192, 128, 128, BF16, 1, "xla"),
]


@pytest.mark.parametrize("impl,tpu,t,d,rot,dtype,rows,want", DOOR)
def test_the_door_takes_what_the_kernels_are_written_for(impl, tpu, t, d, rot, dtype, rows, want,
                                                         monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu" if tpu else "cpu")
    monkeypatch.setattr(kernel_call, "per_device_batch", lambda b: rows)
    assert att.rope_impl(impl, 2, t, d, rot, dtype) == want
    monkeypatch.setenv("DL4J_TPU_PALLAS", "0")         # the helpers' switch turns 'auto' off
    assert att.rope_impl(impl, 2, t, d, rot, dtype) == (want if impl == "pallas" else "xla")


@pytest.mark.parametrize("t,width,want", [(8192, 6144, 128), (8192, 4096, 256), (8192, 512, 2048),
                                          (48, 512, 48), (8192, 2 ** 17, 16)])
def test_a_program_takes_all_columns_of_tokens_up_to_a_block(t, width, want):
    tb = rope_kernels._tokens(t, width)
    assert tb == want and t % tb == 0 and tb % 16 == 0
    assert tb * width <= rope_kernels._BLOCK or tb == 16


#: the three models that build a `GatedAttention` with positions, small
LAYERS = {
    "ouro": dict(n_heads=2, n_kv_heads=2, head_dim=128, rotary_fraction=1.0, rope_theta=1e6,
                 gated=False, qk_norm=False),
    "lfm2": dict(n_heads=4, n_kv_heads=2, head_dim=64, rotary_fraction=1.0, rope_theta=1e6,
                 gated=False, qk_norm=True, qk_norm_zero_centered=False),
    "qwen3next": dict(n_heads=2, n_kv_heads=1, head_dim=256, rotary_fraction=0.25, rope_theta=1e7,
                      gated=True, qk_norm=True),
}


@pytest.mark.parametrize("dtype", [BF16, F32])
@pytest.mark.parametrize("model", sorted(LAYERS))
def test_a_layer_on_a_tpus_path_is_the_layer_on_the_xla_path(model, dtype, rng, monkeypatch):
    """The layer as a TPU would trace it ('auto', the kernels interpreted):
    output and the gradients of the parameters and the input are the XLA
    path's. Only a projection with no norm and no gate in front goes through
    the kernels — behind an XLA norm the pair is SLOWER than the one fusion
    XLA makes of norm, transpose and rotation (PERF.md section 6, PR 46) —
    and a 64-wide head is declined by the door."""
    layer = GatedAttention(**LAYERS[model])
    t, f = 64, 32
    p = layer.init_params(jax.random.PRNGKey(5), it.recurrent(f, t))
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype) if a.ndim == 2 else a, p)
    if layer.qk_norm:
        p.update(q_norm=p["q_norm"] + 0.1 * jnp.arange(layer.head_dim, dtype=F32) / layer.head_dim)
    x = jnp.asarray(rng.standard_normal((2, t, f)), F32).astype(dtype)

    def run():
        def loss(p_, x_):
            y, _ = layer.apply(p_, x_, state={}, train=True, rng=None)
            return jnp.sum(y.astype(F32) ** 2), y
        return jax.tree_util.tree_leaves(jax.value_and_grad(loss, (0, 1), has_aux=True)(p, x))

    xla = run()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernel_call, "interpret", lambda: True)
    with mock.patch.object(rope_kernels, "rope_split_kernels",
                           wraps=rope_kernels.rope_split_kernels) as ran:
        got = run()
    assert ran.call_count == (1 if model == "ouro" else 0)
    eps = float(jnp.finfo(dtype).eps)
    for a, b_ in zip(got, xla):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b_, np.float32),
                                   atol=8 * eps * float(jnp.abs(b_.astype(F32)).max()) + 1e-8)


@pytest.mark.parametrize("why", ["head_64", "odd_t", "model_mesh"])
def test_a_declined_layer_keeps_the_xla_path(why, rng, monkeypatch):
    """Ouro's fields at a head of 64, at tokens that are no whole tiles, and
    under a mesh no kernel can follow: `rope_heads` returns None, no kernel
    runs and the output is the CPU's."""
    fields = dict(LAYERS["ouro"], head_dim=64 if why == "head_64" else 128)
    layer = GatedAttention(**fields)
    t, f = (50 if why == "odd_t" else 64), 32
    p = layer.init_params(jax.random.PRNGKey(6), it.recurrent(f, t))
    x = jnp.asarray(rng.standard_normal((2, t, f)), F32)
    want, _ = layer.apply(p, x, state={}, train=True, rng=None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernel_call, "interpret", lambda: True)
    if why == "model_mesh":
        monkeypatch.setattr(kernel_call, "_kernel_batch_shards", lambda: None)
    z = jnp.zeros((2, t, 6 * fields["head_dim"]), F32)
    assert att.rope_heads(z, (2, 2, 2), (True, True, False), fields["head_dim"],
                          fields["head_dim"], 1e6) is None
    with mock.patch.object(rope_kernels, "rope_split_kernels") as ran:
        got, _ = layer.apply(p, x, state={}, train=True, rng=None)
    assert ran.call_count == 0
    np.testing.assert_array_equal(got, want)


def test_the_neighbours_pairing_lowers_as_before(rng):
    """`rotary(interleave=True)` — `LatentAttention`'s, Kanana's step — is the
    function `_rotary_neighbours` was, operation for operation, now that the
    half-split pairing shares its form."""
    def neighbours(x, rotary_dim, theta, start):   # hybrid._rotary_neighbours at the parent commit
        t, width = x.shape[2], x.shape[3]
        lane = np.arange(width) - start
        inside = (lane >= 0) & (lane < rotary_dim)
        even = lane % 2 == 0
        swap = np.zeros((width, width), np.float32)
        at = np.arange(width)[inside]
        swap[at + np.where(even[inside], 1, -1), at] = 1.0
        pair = jnp.asarray(np.where(inside, lane // 2, 0), F32)
        ang = jnp.arange(t, dtype=F32)[:, None] * theta ** (-2.0 * pair / rotary_dim)
        cos = jnp.where(inside, jnp.cos(ang), 1.0)
        sin = jnp.where(inside, jnp.where(even, -jnp.sin(ang), jnp.sin(ang)), 0.0)
        partner = jnp.einsum("bhtd,de->bhte", x, jnp.asarray(swap, x.dtype),
                             precision=jax.lax.Precision.HIGHEST, preferred_element_type=F32)
        return (x.astype(F32) * cos + partner * sin).astype(x.dtype)

    x = jnp.asarray(rng.standard_normal((2, 3, 21, 16)), BF16)
    for rot, start in ((8, 0), (8, 5), (16, 0)):
        now = jax.make_jaxpr(lambda a: hybrid.rotary(a, rot, 1e6, start, True))(x)
        was = jax.make_jaxpr(lambda a: neighbours(a, rot, 1e6, start))(x)
        assert str(now) == str(was)
        np.testing.assert_array_equal(hybrid.rotary(x, rot, 1e6, start, True), neighbours(x, rot, 1e6, start))


@pytest.mark.parametrize("dtype", [BF16, F32])
@pytest.mark.parametrize("rot,start,width", [(4, 0, 16), (16, 0, 16), (8, 5, 16)])
def test_the_half_split_product_form_is_the_sliced_form(rot, start, width, dtype, rng):
    """`hybrid.rotary`'s half-split pairing as x cos + (x S) sin against the
    slices it replaced (shifted to `start`), to one rounding."""
    x = jnp.asarray(rng.standard_normal((2, 3, 21, width)), F32).astype(dtype)
    want = jnp.concatenate([x[..., :start], before(x[..., start:], rot, THETA)], axis=-1)
    one_rounding(hybrid.rotary(x, rot, THETA, start), want, dtype)


def test_a_step_traced_for_a_tpu_names_both_kernels_and_the_reader_folds_them(rng):
    """Ouro's layer lowered for a TPU: one forward and one backward call
    under names that carry the shape, which `trace_reduce` folds into the
    family; the CPU's lowering holds none."""
    from benchmark import trace_reduce

    layer = GatedAttention(**LAYERS["ouro"])
    t, f = 256, 32
    p = layer.init_params(jax.random.PRNGKey(3), it.recurrent(f, t))
    x = jnp.asarray(rng.standard_normal((2, t, f)), F32)

    def loss(p_, x_):
        return jnp.sum(layer.apply(p_, x_, state={}, train=True, rng=None)[0])

    with mock.patch("jax.default_backend", return_value="tpu"):
        text = jax.export.export(jax.jit(jax.grad(loss)), platforms=["tpu"])(p, x).mlir_module()
    for part in ("fwd", "bwd"):
        name = pk.kernel_name(f"rope_{part}", F32, bh=2 * 6, t=t, d=128, r=128)
        assert name == f"dl4j_rope_{part}_bh12_t256_d128_r128_float32"
        assert text.count(name) >= 1, name
        event = f"%{name}.7 = bf16[2,2,{t},128]{{3,2,1,0}} custom-call(bf16[2,{t},768] %z)"
        assert trace_reduce.describe(event) == f"dl4j_rope_{part}"
    cpu = jax.export.export(jax.jit(jax.grad(loss)), platforms=["cpu"])(p, x).mlir_module()
    assert "dl4j_rope" not in cpu


def test_under_a_data_mesh_each_device_runs_its_own_rows(rng):
    """The kernels inside ONE manual region over 'data': the parts stay
    sharded by rows and the gradient is the unsharded call's."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.parallel import MeshSpec, build_mesh

    if jax.device_count() < 8:
        pytest.skip("needs the 8 virtual devices of tests/conftest.py")
    mesh = build_mesh(MeshSpec(data=8))
    heads, turned, d = (2, 1, 1), (True, True, False), 128
    a = jnp.asarray(rng.standard_normal((8, 32, 4 * d)), F32)

    def f(a_):
        out = kernels(a_, heads, turned, d, d)
        return sum(jnp.sum(o * o * (i + 1)) for i, o in enumerate(out)), out

    (want_loss, want), want_grad = jax.value_and_grad(f, has_aux=True)(a)
    with jax.set_mesh(mesh):
        sharded = jax.device_put(a, NamedSharding(mesh, P("data")))
        (loss, got), grad = jax.jit(jax.value_and_grad(f, has_aux=True))(sharded)
    for g, w in zip(got, want):
        assert g.sharding.spec[0] == "data"
        np.testing.assert_allclose(g, w, atol=1e-6)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(grad, want_grad, atol=1e-5)
