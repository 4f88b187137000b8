"""Hybrid decoder layers: RMS norm, rotary positions, gated softmax
attention with grouped key/value heads, gated-delta-rule linear attention,
routed experts that are told which experts they hold, and the block that
stacks a mixer of either kind on the expert layer (the Qwen3-Next shape;
ROADMAP R3, R4, R8).

All BTF [batch, time, features] like `attention.py`; weights [n_in, n_out],
bias-free. Under the mixed policy the projections run on bf16 operands
(`ops.dot`); norms, the softmax over experts, decays and the delta rule's
state are float32.

  RMSNorm        y = x rsqrt(mean x^2 + eps) (1 + w)   (zero-centred weight;
                 or the plain form .. w)
  rotary         half-split pairing on the first `rotary_dim` of a head
  GatedAttention [q | g | k | v] = x Wqkv; per-head RMS norm of q and k;
                 partial rotary; each key/value head repeated to its query
                 heads; causal softmax through `ops.attention.attend`
                 (the flash kernels where its rule admits them);
                 o sigmoid(g) Wo. Gate, norms and positions can each be
                 left out (plain grouped-query attention)
  GatedDeltaNet  [q | k | v | z] = x Wqkvz, [b | a] = x Wba; short causal
                 depthwise convolution + silu over [q | k | v]; the gated
                 delta rule S <- exp(g) S; S += k (beta (v - S^T k))^T;
                 o = S^T q, run in chunks (`chunk_gated_delta_rule`);
                 gated RMS norm by silu(z); Wout. Between the projections
                 everything is chunk-major [n, b, heads, c, d]: re-tiled
                 once in (`to_chunks`) and once out (`from_chunks`), in
                 the projection's dtype; q and k keep their key heads
  RoutedExperts  a router over ALL experts (top-k of a softmax; or sigmoid
                 scores, chosen by score + a selection bias, weighted by
                 the bare scores), top-k renormalised; the terms of the
                 experts HELD (`experts_held` = first, count) through a
                 sorted buffer of static capacity and XLA's grouped
                 product (`ops.linear.grouped_dot`, which pads widths);
                 SwiGLU or relu^2 experts; a shared expert, gated or not.
                 Its device work is a function of shapes alone; overflow is
                 counted and left out. Counters live in the layer's state
                 (`counters`) and reach `telemetry.fit_log()` once a fit.
  HybridBlock    h = x + mixer(rms(x)); y = h + experts(rms(h))

`to_chunks`, `conv_silu`, `from_chunks` and the row mapping
(`rows_at_a_time`, `over_row_groups`) also serve the state-space mixer of
`ssm.py`, which has the block of ONE sub-layer.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from deeplearning4j_tpu.nn import initializers as init_mod
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.layers.base import REMAT_KEEP, Layer, register_layer
from deeplearning4j_tpu.ops import attention as att
from deeplearning4j_tpu.ops import linear as ops

F32 = jnp.float32


def _w(layer: Layer, rng, shape):
    return init_mod.init(layer.weight_init or "xavier", rng, shape,
                         fan_in=shape[-2], fan_out=shape[-1])


def rms_norm(x, w, eps: float, zero_centered: bool = True):
    """RMS norm over the last axis, computed in float32, in x's dtype."""
    xf = x.astype(F32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w if zero_centered else w)).astype(x.dtype)


def rotary(x, rotary_dim: int, theta: float):
    """Rotary positions on the first `rotary_dim` features of x
    [b, h, t, d]: feature j pairs with j + rotary_dim/2 (half-split), angle
    pos theta^(-2j/rotary_dim). The rest passes through."""
    t, half = x.shape[2], rotary_dim // 2
    j = jnp.arange(half, dtype=F32)
    ang = jnp.arange(t, dtype=F32)[:, None] * theta ** (-2.0 * j / rotary_dim)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(F32)
    a, b, rest = xf[..., :half], xf[..., half:rotary_dim], xf[..., rotary_dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1).astype(x.dtype)


@register_layer
@dataclass
class RMSNorm(Layer):
    """y = x rsqrt(mean x^2 + eps) (1 + w), w from zero; with
    `zero_centered` off the plain form y = .. w, w from one."""

    eps: float = 1e-6
    zero_centered: bool = True

    sp_safe = True  # normalizes the feature axis only

    def output_type(self, input_type):
        return input_type

    def init_params(self, rng, input_type):
        n = input_type.size if isinstance(input_type, it.Recurrent) else input_type.arity()
        return {"w": (jnp.zeros if self.zero_centered else jnp.ones)((n,), F32)}

    def regularizable(self, params):
        return {}

    def apply(self, params, x, *, state, train, rng, mask=None):
        return rms_norm(x, params["w"], self.eps, self.zero_centered), state


# ---------------------------------------------------------------------------
# gated softmax attention
# ---------------------------------------------------------------------------
@register_layer
@dataclass
class GatedAttention(Layer):
    """Causal softmax attention with grouped key/value heads, per-head RMS
    norm of q and k, rotary positions on part of each head and a sigmoid
    output gate. Wqkv [f, (2 n_heads + 2 n_kv_heads) head_dim] = [q | g | k | v].
    Each of the three can be left out (`gated`, `qk_norm`, `rotary_fraction`
    0): then Wqkv = [q | k | v] and there are no norm weights — plain
    grouped-query attention that knows no positions."""

    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    rotary_fraction: float = 0.25
    rope_theta: float = 1e7
    eps: float = 1e-6
    gated: bool = True
    qk_norm: bool = True

    def output_type(self, input_type):
        return input_type

    def init_params(self, rng, input_type):
        f = input_type.size
        h, kv, d = self.n_heads, self.n_kv_heads, self.head_dim
        if h % kv:
            raise ValueError(f"n_kv_heads={kv} must divide n_heads={h}")
        r = jax.random.split(rng, 2)
        p = {"Wqkv": _w(self, r[0], (f, ((2 if self.gated else 1) * h + 2 * kv) * d)),
             "Wo": _w(self, r[1], (h * d, f))}
        if self.qk_norm:
            p.update(q_norm=jnp.zeros((d,), F32), k_norm=jnp.zeros((d,), F32))
        return p

    def regularizable(self, params):
        return {k: v for k, v in params.items() if k.startswith("W")}

    def apply(self, params, x, *, state, train, rng, mask=None):
        b, t, _ = x.shape
        h, kv, d = self.n_heads, self.n_kv_heads, self.head_dim
        z = ops.dot(x, params["Wqkv"])
        if self.gated:
            q, g, k, v = jnp.split(z, [h * d, 2 * h * d, (2 * h + kv) * d], axis=-1)
        else:
            q, k, v = jnp.split(z, [h * d, (h + kv) * d], axis=-1)

        def heads(a, n):  # [b, t, n d] -> [b, n, t, d]
            return a.reshape(b, t, n, d).transpose(0, 2, 1, 3)

        rot = int(d * self.rotary_fraction)

        def prepared(a, n, norm):  # a head's norm, then its positions
            a = heads(a, n)
            if self.qk_norm:
                a = rms_norm(a, params[norm], self.eps)
            return rotary(a, rot, self.rope_theta) if rot else a

        q, k = prepared(q, h, "q_norm"), prepared(k, kv, "k_norm")
        k, v = (jnp.repeat(a, h // kv, axis=1) for a in (k, heads(v, kv)))
        o = att.attend(q, k, v, causal=True, mask=mask)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, h * d)
        if self.gated:
            o = o * jax.nn.sigmoid(g.astype(F32)).astype(o.dtype)
        y = ops.dot(o, params["Wo"])
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, state


# ---------------------------------------------------------------------------
# gated delta rule
# ---------------------------------------------------------------------------
#: tokens a chunk of the delta rule (the benchmark's reference segments and
#: its scan's operation count assume the same)
CHUNK = 64


def _mm(spec, a, b):
    """A batched product named by its axes: no operand is transposed in
    memory to fit `matmul`'s [.., i, j] x [.., j, k]."""
    return jnp.einsum(spec, a, b, precision=ops._precision())


def _chunk_step(s, ab):
    """The body of the scan over chunks: S' = A S + B. Emits the state the
    chunk STARTS from."""
    a_i, b_i = ab
    return _mm("bhij,bhjv->bhiv", a_i, s) + b_i, s


def to_chunks(a, chunk: int = CHUNK):
    """[b, t, h, ...] -> [n, b, h, c, ...]: chunk-major, a head's chunk of
    `chunk` tokens contiguous, the time zero-padded to whole chunks. With a
    last axis of 128 this moves whole tiles (c tokens x 128 lanes of one
    head stay together); it is the ONE re-tiling on the way into a chunked
    recurrence, done on the narrowest form (the bf16 projection)."""
    b, t = a.shape[:2]
    pad = (-t) % chunk
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    a = a.reshape((b, (t + pad) // chunk, chunk) + a.shape[2:])
    return a.transpose((1, 0, 3, 2) + tuple(range(4, a.ndim)))


def from_chunks(a, t: int):
    """[n, b, h, c, ...] -> [b, t, h, ...]: the way back, once."""
    n, b, h, c = a.shape[:4]
    a = a.transpose((1, 0, 3, 2) + tuple(range(4, a.ndim)))
    return a.reshape((b, n * c, h) + a.shape[4:])[:, :t]


def chunk_gated_delta_rule(q, k, v, g, beta):
    """The gated delta rule over chunks of `CHUNK` tokens, chunk-major
    (`to_chunks`): q, k [n, b, hk, c, dk] (normalised and scaled by the
    caller), v [n, b, hv, c, dv], g (log decay, <= 0) and beta [n, b, hv, c],
    all float32 -> o [n, b, hv, c, dv]. Value head j reads key head
    j // (hv / hk); q and k are never repeated to hv heads: K K^T and Q K^T
    are computed once a key head and every array of a value head is held
    as [.., hk, hv / hk, ..] beside them.

    Per value head, S_0 = 0 and for every token S <- exp(g) S;
    S <- S + k (beta (v - S^T k))^T; o = S^T q. Within a chunk the writes
    d_j = beta_j (v_j - ...) solve a unit lower-triangular system
    (I + A) D = U - W S_0 with A_jl = beta_j (k_j . k_l) exp(G_j - G_l),
    l < j, G the running sum of g in the chunk; a scan over chunks carries
    S (one [dk, dk] x [dk, dv] product a chunk and head). Everything else is
    batched over all chunks in the layout it arrives in — no array is
    re-tiled here; autodiff through both gives the backward in chunks too."""
    mm = _mm
    n, b, hk, c, dk = q.shape
    hv, dv = v.shape[2], v.shape[-1]

    def per_key(a):   # [n, b, hv, ...] -> [n, b, hk, hv / hk, ...]: no data moves
        return a.reshape((n, b, hk, hv // hk) + a.shape[3:])

    v, g, beta = per_key(v), per_key(g), per_key(beta)
    gc = jnp.cumsum(g, axis=-1)                                  # [n, b, hk, rep, c]
    i = jnp.arange(c)
    lower, strict = i[:, None] >= i[None, :], i[:, None] > i[None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, gc[..., :, None] - gc[..., None, :], 0.0)), 0.0)
    # once a key head, shared by its value heads
    kk = mm("nbhid,nbhjd->nbhij", k, k)[:, :, :, None]
    qk = mm("nbhid,nbhjd->nbhij", q, k)[:, :, :, None]
    q, k = q[:, :, :, None], k[:, :, :, None]                    # [n, b, hk, 1, c, dk]
    a_mat = jnp.where(strict, kk * beta[..., None] * decay, 0.0) + jnp.eye(c, dtype=F32)
    # W is solved for with its sign turned (on the small factor): what
    # follows only ever subtracts it, and a sum's cotangent needs no pass
    rhs = jnp.concatenate([v * beta[..., None],
                           k * (-beta * jnp.exp(gc))[..., None]], -1)
    sol = jax.scipy.linalg.solve_triangular(a_mat, rhs, lower=True,
                                            unit_diagonal=True)
    u, w = sol[..., :dv], sol[..., dv:]                          # w = -W
    qk = jnp.where(lower, qk * decay, 0.0)
    q_dec = q * jnp.exp(gc)[..., None]
    k_dec = k * jnp.exp(gc[..., -1:] - gc)[..., None]
    # the state across chunks is linear in itself: S' = A S + B with
    # A = exp(G_c) I - K_dec^T W, B = K_dec^T U. A and B come from batched
    # products over all chunks; the scan's body is one product and one sum
    last = jnp.exp(gc[..., -1])[..., None, None]
    a_all = last * jnp.eye(dk, dtype=F32) + mm("nbhrci,nbhrcj->nbhrij", k_dec, w)
    b_all = mm("nbhrci,nbhrcv->nbhriv", k_dec, u)

    _, s_all = lax.scan(_chunk_step, jnp.zeros((b, hv, dk, dv), F32),
                        (a_all.reshape(n, b, hv, dk, dk), b_all.reshape(n, b, hv, dk, dv)))
    s_all = per_key(s_all)
    o = mm("nbhrck,nbhrkv->nbhrcv", q_dec, s_all) + mm(
        "nbhrij,nbhrjv->nbhriv", qk, u + mm("nbhrck,nbhrkv->nbhrcv", w, s_all))
    return o.reshape(n, b, hv, c, dv)


def _shift(a, s: int):
    """Tokens of every chunk of a [n, r, h, c, d] moved by s places: token i
    takes token i - s of its chunk (s < 0: a later one), zeros where the
    chunk has none."""
    return lax.pad(a, jnp.zeros((), a.dtype), ((0, 0, 0),) * 3 + ((s, -s, 0), (0, 0, 0)))


def _next_chunk(a, step: int):
    """Chunk n takes chunk n - step (step +1: the one before; -1: after);
    zeros beyond the ends."""
    zero = jnp.zeros_like(a[:1])
    return jnp.concatenate([zero, a[:-1]] if step > 0 else [a[1:], zero], axis=0)


def _rows(a, lo: int, hi: int):
    """Zero token rows put before and after every chunk's."""
    return jnp.pad(a, ((0, 0),) * 3 + ((lo, hi), (0, 0)))


def _tail_before(x, cw: int):
    """[the last cw - 1 tokens of the chunk before | as many zeros], float32:
    what the first cw - 1 tokens of a chunk read across its border."""
    return _rows(_next_chunk(x[..., x.shape[3] - (cw - 1):, :], 1).astype(F32), 0, cw - 1)


def _conv_pre(x, w, b=None):
    """The short causal depthwise convolution over the tokens of x
    [n, r, h, c, d] (chunk-major, any float dtype), w [cw, h, 1, d], tap
    cw - 1 - s on the token s places back, plus the bias b [h, 1, d] where
    there is one -> float32. Token i of a chunk
    reads its own chunk shifted, zeros let in (the rows are shifted in the
    dtype they arrive in and widened after: a shift moves half the bytes),
    plus, for i < cw - 1, the same taps over the tail of the chunk before:
    a sliver of cw - 1 of c rows."""
    c, cw = x.shape[3], w.shape[0]
    taps = [w[cw - 1 - s] for s in range(cw)]
    acc = sum(_shift(x, s).astype(F32) * taps[s] for s in range(cw))
    tail = _tail_before(x, cw)
    halo = sum(_shift(tail, s) * taps[s] for s in range(cw))[..., cw - 1:, :]
    out = acc + _rows(halo, 0, c - (cw - 1))
    return out if b is None else out + b


def conv_silu(x, w, b=None):
    """silu(`_conv_pre`(x, w, b)). Its backward is written out so that the
    cotangents of the cw shifted reads of x add up in float32 and round to
    x's dtype once (autodiff would round each and add in that dtype — x is
    the bf16 projection under the mixed policy)."""
    return _conv_silu(x, w, b)


@jax.custom_vjp
def _conv_silu(x, w, b):
    return jax.nn.silu(_conv_pre(x, w, b))


def _conv_silu_fwd(x, w, b):
    pre = _conv_pre(x, w, b)
    return jax.nn.silu(pre), (x, w, b, pre)


def _conv_silu_bwd(res, dy):
    x, w, b, pre = res
    c, cw = x.shape[3], w.shape[0]
    taps = [w[cw - 1 - s] for s in range(cw)]
    sg = jax.nn.sigmoid(pre)
    d = dy * (sg * (1.0 + pre * (1.0 - sg)))
    # token j is read by tokens j + s: of its own chunk, or (from the last
    # cw - 1 places) by the first tokens of the chunk after
    dx = sum(_shift(d, -s) * taps[s] for s in range(cw))
    head = _rows(_next_chunk(d[..., :cw - 1, :], -1), cw - 1, 0)
    into_next = sum(_shift(head, -s) * taps[s] for s in range(cw))[..., :cw - 1, :]
    dx = dx + _rows(into_next, c - (cw - 1), 0)
    tail = _tail_before(x, cw)
    dw = [jnp.sum(d * _shift(x, s).astype(F32), axis=(0, 1, 3), keepdims=True)[0, 0]
          + jnp.sum(d[..., :cw - 1, :] * _shift(tail, s)[..., cw - 1:, :],
                    axis=(0, 1, 3), keepdims=True)[0, 0] for s in range(cw)]
    db = None if b is None else jnp.sum(d, axis=(0, 1, 3), keepdims=True)[0, 0]
    return dx.astype(x.dtype), jnp.stack(dw[::-1]), db


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


#: float32 bytes of convolution input a chunked core takes at a time: beyond
#: it the rows are mapped, each group a checkpoint, so that the core's
#: working set is one group's, not the batch's
CORE_BYTES = 2 ** 28


def rows_at_a_time(b: int, row_bytes: int, limit: int = CORE_BYTES) -> int:
    """The largest divisor of the batch whose rows stay within `limit`."""
    rows = min(b, max(1, limit // row_bytes))
    while b % rows:        # groups of equal size
        rows -= 1
    return rows


def over_row_groups(core, arrays, rows: int, chunk: int = CHUNK):
    """`core` between a layer's projections, a group of `rows` rows at a
    time. `arrays`: pairs (a [b, t, ..], the shape a token's features are
    split into — () as they are). Each is re-tiled ONCE, each group's rows
    on their own, in the dtype it arrives in: [b / rows, n, rows, heads, c,
    ..] (`to_chunks`), and `core(*group)` runs on the whole batch when it is
    one group, else mapped over the groups, each a checkpoint. What comes
    back then carries the groups in front and is tagged `REMAT_KEEP`: kept
    by a block's 'full' remat, because the groups rerun in their own
    backward and need not run in the block's recompute too."""
    b, t = arrays[0][0].shape[:2]
    args = [a.reshape((b // rows, rows, t) + (tuple(heads) or a.shape[2:]))
            for a, heads in arrays]
    args = tuple(jax.vmap(lambda g: to_chunks(g, chunk))(a) for a in args)
    if rows == b:
        return core(*(a[0] for a in args))
    out = lax.map(jax.checkpoint(lambda a: core(*a)), args)
    return jax.tree_util.tree_map(lambda y: checkpoint_name(y, REMAT_KEEP), out)


@register_layer
@dataclass
class GatedDeltaNet(Layer):
    """Gated-delta-rule linear attention over [b, t, f] (see the module
    docstring). Wqkvz [f, 2 n_key_heads key_dim + 2 n_value_heads value_dim];
    conv [conv_width, 2 n_key_heads key_dim + n_value_heads value_dim]."""

    n_key_heads: int = 16
    n_value_heads: int = 32
    key_dim: int = 128
    value_dim: int = 128
    conv_width: int = 4
    eps: float = 1e-6

    def output_type(self, input_type):
        return input_type

    def init_params(self, rng, input_type):
        f = input_type.size
        hk, hv = self.n_key_heads, self.n_value_heads
        if hv % hk:
            raise ValueError(f"n_key_heads={hk} must divide n_value_heads={hv}")
        key, val = hk * self.key_dim, hv * self.value_dim
        r = jax.random.split(rng, 6)
        return {
            "Wqkvz": _w(self, r[0], (f, 2 * key + 2 * val)),
            "Wba": _w(self, r[1], (f, 2 * hv)),
            "conv": jax.random.uniform(r[2], (self.conv_width, 2 * key + val), F32,
                                       -1.0, 1.0) * self.conv_width ** -0.5,
            # decay exp(-A softplus(a + dt_bias)) starts close to 1
            "A_log": jnp.log(jax.random.uniform(r[3], (hv,), F32, 0.05, 0.3)),
            "dt_bias": jax.random.uniform(r[4], (hv,), F32, -4.0, -2.0),
            "norm": jnp.ones((self.value_dim,), F32),
            "Wout": _w(self, r[5], (val, f)),
        }

    def regularizable(self, params):
        return {k: v for k, v in params.items() if k.startswith("W")}

    def _core(self, params, t, qk, v, ba, z, mask=None):
        """Everything between the projections, for rows r of t tokens.
        Chunk-major (`to_chunks`) and in the projection's dtype: qk
        [n, r, 2 hk, c, dk], v and z [n, r, hv, c, dv], ba [n, r, 2 hv, c],
        mask [n, r, 1, c] -> [r, t, hv dv]. The short convolution, the
        decays, the delta rule, the gated norm — and the one re-tiling
        back, of the result in the projection's dtype."""
        hk, hv, cw = self.n_key_heads, self.n_value_heads, self.conv_width
        key = hk * self.key_dim
        qk = conv_silu(qk, params["conv"][:, :2 * key].reshape(cw, 2 * hk, 1, -1))
        v = conv_silu(v, params["conv"][:, 2 * key:].reshape(cw, hv, 1, -1))

        def l2(a):
            return a * lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

        ba = ba.astype(F32)
        beta = jax.nn.sigmoid(ba[:, :, :hv])
        g = -jnp.exp(params["A_log"])[:, None] * jax.nn.softplus(
            ba[:, :, hv:] + params["dt_bias"][:, None])
        if mask is not None:  # a padded token writes nothing, keeps the state
            beta, g = beta * mask, g * mask
        o = chunk_gated_delta_rule(l2(qk[:, :, :hk]) * self.key_dim ** -0.5,
                                   l2(qk[:, :, hk:]), v, g, beta)
        o = rms_norm(o, params["norm"], self.eps, zero_centered=False)
        y = from_chunks((o * jax.nn.silu(z.astype(F32))).astype(z.dtype), t)
        return y.reshape(y.shape[:2] + (-1,))

    #: `CORE_BYTES` for this layer. At 8192 tokens x 8192 channels a row the
    #: convolution input is 268 MB, and so is each of the solve's right-hand
    #: side and solution and of the scan's A, B and S; q, k, v, the decayed
    #: q and k, the output and the cotangent of each come to as much again:
    #: some 3 GB a row while its backward runs
    CORE_BYTES = CORE_BYTES

    def apply(self, params, x, *, state, train, rng, mask=None):
        b, t, _ = x.shape
        hk, hv, dk, dv = self.n_key_heads, self.n_value_heads, self.key_dim, self.value_dim
        key, val = hk * dk, hv * dv
        qkvz = ops.dot(x, params["Wqkvz"])
        qkv, z = qkvz[..., :2 * key + val], qkvz[..., 2 * key + val:]
        ba = ops.dot(x, params["Wba"])
        if mask is not None:  # a padded token enters no convolution window
            qkv = qkv * mask[..., None].astype(qkv.dtype)
        rows = rows_at_a_time(b, t * (2 * key + val) * 4, self.CORE_BYTES)
        # z goes along so that the gate is taken where the rule's output lies
        args = [(qkv[..., :2 * key], (2 * hk, dk)), (qkv[..., 2 * key:], (hv, dv)),
                (ba, ()), (z, (hv, dv))]
        if mask is not None:
            args.append((mask.astype(F32)[..., None], ()))
        core = {k: params[k] for k in ("conv", "A_log", "dt_bias", "norm")}
        y = over_row_groups(lambda *a: self._core(core, t, *a), args, rows)
        y = ops.dot(y.reshape(b, t, val), params["Wout"])
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, state


# ---------------------------------------------------------------------------
# routed experts
# ---------------------------------------------------------------------------
def _swiglu(h):
    gate, up = jnp.split(h, 2, axis=-1)
    return jax.nn.silu(gate) * up


def _relu2(h):
    return jnp.square(jax.nn.relu(h))


#: an expert's non-linearity between its two products -> (the function, how
#: many times the expert's width its first matrix is wide, that matrix's
#: name): "swiglu" silu(x Wg) (x Wu) Wd with [gate | up] one matrix;
#: "relu2" relu(x Wu)^2 Wd
EXPERT_ACTS = {"swiglu": (_swiglu, 2, "Wgu"), "relu2": (_relu2, 1, "Wu")}


# The sorted buffer is a permutation of the (slot, token) assignments cut to
# its capacity, so both ways across it are GATHERS, forward and backward:
# `order` maps a sorted position to its assignment, `inv` an assignment to
# its position. (Autodiff would transpose each gather into a scatter-add of
# tokens x top_k rows, which the chip runs several times slower.) An
# assignment's number is slot * n + token: the k slots of a token are then k
# slabs [n, f] and their sum needs no re-tiling of a [n, k, f] array.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_buffer(xf, order, inv, cap: int):
    """xf [n, f] -> the buffer's rows [cap, f]: the token of each of the
    first `cap` sorted assignments."""
    return xf[(order % xf.shape[0])[:cap]]


def _to_buffer_fwd(xf, order, inv, cap):
    return _to_buffer(xf, order, inv, cap), (inv, xf.shape[0])


def _to_buffer_bwd(cap, res, g):
    inv, n = res
    g = jnp.pad(g, ((0, inv.shape[0] - cap), (0, 0)))   # assignments cut off: no gradient
    return g[inv].reshape(-1, n, g.shape[-1]).astype(F32).sum(axis=0).astype(g.dtype), None, None


_to_buffer.defvjp(_to_buffer_fwd, _to_buffer_bwd)


@jax.custom_vjp
def _from_buffer(ys, wt, order, inv):
    """Buffer rows ys [cap, f] back to tokens: out[n] = sum over the
    token's k slots of wt[slot, n] ys[position of (slot, n)], float32.
    `wt` [k, n] is zero for a slot that is not in the buffer."""
    k, n = wt.shape
    full = jnp.pad(ys, ((0, k * n - ys.shape[0]), (0, 0)))
    return jnp.sum(full[inv].reshape(k, n, -1).astype(F32) * wt[..., None], axis=0)


def _from_buffer_fwd(ys, wt, order, inv):
    return _from_buffer(ys, wt, order, inv), (ys, wt, order, inv)


def _from_buffer_bwd(res, g):
    ys, wt, order, inv = res
    (k, n), cap = wt.shape, ys.shape[0]
    src = order[:cap]
    # the cotangent crosses the buffer in the buffer's dtype, like the rows;
    # a slot's weight gradient <g[token], ys[position]> is taken in buffer
    # order from the same gathered rows and carried back as a vector
    rows = g.astype(ys.dtype)[src % n].astype(F32)
    d_ys = (rows * wt.reshape(-1)[src][:, None]).astype(ys.dtype)
    dots = jnp.pad(jnp.sum(rows * ys.astype(F32), axis=-1), (0, k * n - cap))
    return d_ys, dots[inv].reshape(k, n), None, None


_from_buffer.defvjp(_from_buffer_fwd, _from_buffer_bwd)


@register_layer
@dataclass
class RoutedExperts(Layer):
    """Experts behind a router, for a rank that holds `experts_held` =
    (first, count) of `n_experts` (default: all), plus a shared expert. The
    router scores all `n_experts`, keeps the `top_k` largest and
    renormalises them over the chosen wherever they live; this layer adds
    the terms of its own experts and leaves the others' out (on one chip it
    runs without the exchange that would bring other ranks' tokens).

    Two recipes share everything below the scores. `scoring` "softmax":
    the weights are the top-k of the softmax. "sigmoid": every expert is
    scored sigmoid(x Wr) on its own; the k are CHOSEN by score +
    `select_bias` (a leaf no gradient reaches: it chooses, it does not
    weigh), weighted by the bare scores, renormalised, and scaled by
    `routed_scale`. `expert_act` (`EXPERT_ACTS`) is the non-linearity of
    routed and shared experts alike; `shared_gated` multiplies the shared
    expert by sigmoid(x w) or adds it as it is.

    Device work is a function of shapes alone: the (token, expert)
    assignments of the held experts are sorted by expert into a buffer of
    `capacity_factor` x the expected count (rows x top_k x count /
    n_experts), the grouped product runs over the whole buffer (the padding
    belongs to the last group and is computed), and the rows are gathered
    back weighted. Assignments beyond the buffer are dropped and counted.

    Both products go through `ops.linear.grouped_dot`, which zero-pads a
    width to the next multiple of 512 where that adds at most a quarter:
    libtpu tiles each width of its grouped product by the largest of 512,
    256, 128 that divides it, and 2688 x 1856 (tiles of 128) ran at 25-33
    TFLOP/s on a v5e where 3072 x 2048 runs at 133-148. The matrices are
    padded as the step is traced, h stays padded between the products, the
    model width is padded and cut at token level; the parameters keep their
    published shapes, and aligned widths take the call they always took.

    State `counters` (int32, wrapping; per-fit differences are exact):
    `steps`, `load` [count] assignments routed to each held expert,
    `dropped`, `capacity` (buffer rows offered), `ratio_sum` (float32 sum
    over steps of max-over-mean load). `telemetry.fit_log()` reports them
    per fit under `experts` (`counter_summary`)."""

    n_experts: int = 512
    top_k: int = 10
    expert_width: int = 512
    shared_width: int = 512
    experts_held: Optional[Sequence[int]] = None
    capacity_factor: float = 1.25
    norm_topk: bool = True
    scoring: str = "softmax"
    routed_scale: float = 1.0
    expert_act: str = "swiglu"
    shared_gated: bool = True

    def held(self):
        return tuple(self.experts_held) if self.experts_held else (0, self.n_experts)

    def _act(self):
        if self.expert_act not in EXPERT_ACTS:
            raise ValueError(f"expert_act={self.expert_act!r}: one of {sorted(EXPERT_ACTS)}")
        return EXPERT_ACTS[self.expert_act]

    def capacity(self, rows: int) -> int:
        """Buffer rows for `rows` tokens: the factor times the expected
        count, to a multiple of 128, at most every assignment."""
        _, count = self.held()
        expected = rows * self.top_k * count / self.n_experts
        c = -(-int(self.capacity_factor * expected) // 128) * 128
        return max(1, min(c, rows * self.top_k))

    def output_type(self, input_type):
        return input_type

    def init_params(self, rng, input_type):
        f = input_type.size
        first, count = self.held()
        if first < 0 or first + count > self.n_experts:
            raise ValueError(f"experts_held={self.experts_held} outside 0..{self.n_experts}")
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring={self.scoring!r}: 'softmax' or 'sigmoid'")
        e, s = self.expert_width, self.shared_width
        _, wide, up = self._act()
        r = jax.random.split(rng, 6)
        p = {"router": _w(self, r[0], (f, self.n_experts)),
             up: _w(self, r[1], (count, f, wide * e)),
             "Wd": _w(self, r[2], (count, e, f)),
             "shared_" + up: _w(self, r[3], (f, wide * s)),
             "shared_Wd": _w(self, r[4], (s, f))}
        if self.shared_gated:
            p["shared_gate"] = _w(self, r[5], (f, 1))
        if self.scoring == "sigmoid":
            p["select_bias"] = jnp.zeros((self.n_experts,), F32)
        return p

    def init_state(self, input_type):
        _, count = self.held()
        zero = lambda: jnp.zeros((), jnp.int32)  # noqa: E731 — a buffer each: state is donated
        return {"counters": {"steps": zero(), "load": jnp.zeros((count,), jnp.int32),
                             "dropped": zero(), "capacity": zero(),
                             "ratio_sum": jnp.zeros((), F32)}}

    def regularizable(self, params):
        return {k: v for k, v in params.items() if "W" in k}

    def counter_summary(self, added):
        """Per-step means of the counters over a fit, under `experts`."""
        steps = int(added["steps"][0])
        routed, dropped = int(added["load"].sum()), int(added["dropped"][0])
        return "experts", {
            "steps": steps,
            "assignments_per_step": routed / max(steps, 1),
            "load_max_over_mean": float(added["ratio_sum"][0]) / max(steps, 1),
            "dropped_assignments": dropped,
            "capacity_fill": (routed - dropped) / max(int(added["capacity"][0]), 1),
        }

    def route(self, params, xf):
        """(weights [n, top_k] float32, expert ids [n, top_k])."""
        logits = jnp.matmul(xf.astype(F32), params["router"],
                            precision=lax.Precision.HIGHEST)
        if self.scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            _, idx = lax.top_k(scores + lax.stop_gradient(params["select_bias"]), self.top_k)
            top = jnp.take_along_axis(scores, idx, axis=-1)
            if self.norm_topk:
                top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
            return top * self.routed_scale, idx
        top, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), self.top_k)
        if self.norm_topk:
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        return top, idx

    def routed(self, params, xf, top, idx):
        """The held experts' terms for tokens xf [n, f] -> ([n, f] float32,
        routed count per held expert, dropped assignments)."""
        n, f = xf.shape
        k = self.top_k
        first, count = self.held()
        cap = self.capacity(n)
        local = idx.T - first                   # [k, n]: assignment = slot * n + token
        key = jnp.where((local >= 0) & (local < count), local, count).reshape(-1)
        order = jnp.argsort(key, stable=True)   # by expert; what is held comes first
        inv = jnp.argsort(order)
        starts = jnp.searchsorted(key[order], jnp.arange(count + 1), side="left")
        bounds = jnp.minimum(starts, cap)
        sizes = bounds[1:] - bounds[:-1]
        sizes = sizes.at[-1].add(cap - bounds[-1])   # the padding is computed
        # the buffer is born at the width the grouped product runs well at:
        # zero columns added to the TOKENS here and cut from the tokens below.
        # The barrier keeps XLA from moving the pad behind the gather, where it
        # is a pass over the buffer (1.83 ms, 8 a step at Nemotron's size)
        padded = ops.grouped_width(f)
        if padded != f:
            xf = lax.optimization_barrier(jnp.pad(xf, ((0, 0), (0, padded - f))))
        xs = _to_buffer(xf, order, inv, cap)
        act, wide, up = self._act()
        ys = ops.grouped_dot(act(ops.grouped_dot(xs, params[up], sizes, wide)),
                             params["Wd"], sizes)
        # a slot counts when its expert is held and its position is inside the
        # buffer; the rows of the others (the last group's padding) weigh 0
        kept = (key < count) & (inv < cap)
        out = _from_buffer(ys, jnp.where(kept, top.T.reshape(-1), 0.0).reshape(k, n),
                           order, inv)[:, :f]
        load = (starts[1:] - starts[:-1]).astype(jnp.int32)
        dropped = jnp.maximum(starts[-1] - cap, 0).astype(jnp.int32)
        return out, load, dropped

    def apply(self, params, x, *, state, train, rng, mask=None):
        shape = x.shape
        xf = x.reshape(-1, shape[-1])
        top, idx = self.route(params, xf)
        out, load, dropped = self.routed(params, xf, top, idx)
        act, _, up = self._act()
        gate = (jax.nn.sigmoid(ops.dot(xf, params["shared_gate"]).astype(F32))
                if self.shared_gated else 1.0)
        shared = ops.dot(act(ops.dot(xf, params["shared_" + up])), params["shared_Wd"])
        y = (out + gate * shared.astype(F32)).astype(x.dtype).reshape(shape)
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        if train:
            c = state["counters"]
            mean = jnp.maximum(jnp.mean(load.astype(F32)), 1e-9)
            state = {"counters": {
                "steps": c["steps"] + 1, "load": c["load"] + load,
                "dropped": c["dropped"] + dropped,
                "capacity": c["capacity"] + self.capacity(xf.shape[0]),
                "ratio_sum": c["ratio_sum"] + jnp.max(load.astype(F32)) / mean}}
        return y, state


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------
@register_layer
@dataclass
class HybridBlock(Layer):
    """h = x + mixer(rms(x)); y = h + experts(rms(h)), `mixer` "delta"
    (GatedDeltaNet) or "attention" (GatedAttention). One Layer so networks
    stay flat lists and `remat` wraps a whole block; params nest the
    sublayers' (`norm1`, `mixer`, `norm2`, `moe`), state is the experts'."""

    mixer: str = "delta"
    eps: float = 1e-6
    # gated softmax attention
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    rotary_fraction: float = 0.25
    rope_theta: float = 1e7
    # gated delta rule
    n_key_heads: int = 16
    n_value_heads: int = 32
    key_dim: int = 128
    value_dim: int = 128
    conv_width: int = 4
    # routed experts
    n_experts: int = 512
    top_k: int = 10
    expert_width: int = 512
    shared_width: int = 512
    experts_held: Optional[Sequence[int]] = None
    capacity_factor: float = 1.25
    norm_topk: bool = True

    def output_type(self, input_type):
        return input_type

    def _mixer(self):
        if self.mixer == "attention":
            return GatedAttention(
                n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                head_dim=self.head_dim, rotary_fraction=self.rotary_fraction,
                rope_theta=self.rope_theta, eps=self.eps,
                weight_init=self.weight_init)
        if self.mixer == "delta":
            return GatedDeltaNet(
                n_key_heads=self.n_key_heads, n_value_heads=self.n_value_heads,
                key_dim=self.key_dim, value_dim=self.value_dim,
                conv_width=self.conv_width, eps=self.eps,
                weight_init=self.weight_init)
        raise ValueError(f"mixer={self.mixer!r}: 'delta' or 'attention'")

    def _moe(self):
        return RoutedExperts(
            n_experts=self.n_experts, top_k=self.top_k,
            expert_width=self.expert_width, shared_width=self.shared_width,
            experts_held=self.experts_held, capacity_factor=self.capacity_factor,
            norm_topk=self.norm_topk, weight_init=self.weight_init)

    def init_params(self, rng, input_type):
        f = input_type.size
        r = jax.random.split(rng, 2)
        return {"norm1": {"w": jnp.zeros((f,), F32)},
                "mixer": self._mixer().init_params(r[0], input_type),
                "norm2": {"w": jnp.zeros((f,), F32)},
                "moe": self._moe().init_params(r[1], input_type)}

    def init_state(self, input_type):
        return self._moe().init_state(input_type)

    def counter_summary(self, added):
        return self._moe().counter_summary(added)

    def regularizable(self, params):
        out = {"mixer/" + k: v for k, v in self._mixer().regularizable(params["mixer"]).items()}
        out.update({"moe/" + k: v for k, v in self._moe().regularizable(params["moe"]).items()})
        return out

    def apply(self, params, x, *, state, train, rng, mask=None):
        a, _ = self._mixer().apply(
            params["mixer"], rms_norm(x, params["norm1"]["w"], self.eps),
            state={}, train=train, rng=rng, mask=mask)
        h = x + a
        m, state = self._moe().apply(
            params["moe"], rms_norm(h, params["norm2"]["w"], self.eps),
            state=state, train=train, rng=rng, mask=mask)
        return h + m, state
