"""Hybrid decoder layers: RMS norm, rotary positions, gated softmax
attention with grouped key/value heads, gated-delta-rule linear attention,
routed experts that are told which experts they hold, and the block that
stacks a mixer of either kind on the expert layer (the Qwen3-Next shape;
ROADMAP R3, R4, R8).

All BTF [batch, time, features] like `attention.py`; weights [n_in, n_out],
bias-free. Under the mixed policy the projections run on bf16 operands
(`ops.dot`); norms, the softmax over experts, decays and the delta rule's
state are float32.

  RMSNorm        y = x rsqrt(mean x^2 + eps) (1 + w)   (zero-centred weight)
  rotary         half-split pairing on the first `rotary_dim` of a head
  GatedAttention [q | g | k | v] = x Wqkv; per-head RMS norm of q and k;
                 partial rotary; each key/value head repeated to its query
                 heads; causal softmax (the flash kernel where
                 MultiHeadAttention admits it); o sigmoid(g) Wo
  GatedDeltaNet  [q | k | v | z] = x Wqkvz, [b | a] = x Wba; short causal
                 depthwise convolution + silu over [q | k | v]; the gated
                 delta rule S <- exp(g) S; S += k (beta (v - S^T k))^T;
                 o = S^T q, run in chunks (`chunk_gated_delta_rule`);
                 gated RMS norm by silu(z); Wout
  RoutedExperts  softmax router over ALL experts, top-k renormalised; the
                 terms of the experts HELD (`experts_held` = first, count)
                 through a sorted buffer of static capacity and
                 `lax.ragged_dot`; a gated shared expert. Its device work
                 is a function of shapes alone; overflow is counted and
                 left out. Counters live in the layer's state (`counters`)
                 and reach `telemetry.fit_log()` once a fit.
  HybridBlock    h = x + mixer(rms(x)); y = h + experts(rms(h))
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
from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttention
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
    """y = x rsqrt(mean x^2 + eps) (1 + w), w from zero."""

    eps: float = 1e-6

    sp_safe = True  # normalizes the feature axis only

    def output_type(self, input_type):
        return input_type

    def init_params(self, rng, input_type):
        n = input_type.size if isinstance(input_type, it.Recurrent) else input_type.arity()
        return {"w": jnp.zeros((n,), F32)}

    def regularizable(self, params):
        return {}

    def apply(self, params, x, *, state, train, rng, mask=None):
        return rms_norm(x, params["w"], self.eps), state


# ---------------------------------------------------------------------------
# gated softmax attention
# ---------------------------------------------------------------------------
@register_layer
@dataclass
class GatedAttention(Layer):
    """Causal softmax attention with grouped key/value heads, per-head RMS
    norm of q and k, rotary positions on part of each head and a sigmoid
    output gate. Wqkv [f, (2 n_heads + 2 n_kv_heads) head_dim] = [q | g | k | v]."""

    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    rotary_fraction: float = 0.25
    rope_theta: float = 1e7
    eps: float = 1e-6

    def output_type(self, input_type):
        return input_type

    def init_params(self, rng, input_type):
        f = input_type.size
        h, kv, d = self.n_heads, self.n_kv_heads, self.head_dim
        if h % kv:
            raise ValueError(f"n_kv_heads={kv} must divide n_heads={h}")
        r = jax.random.split(rng, 2)
        return {"Wqkv": _w(self, r[0], (f, (2 * h + 2 * kv) * d)),
                "q_norm": jnp.zeros((d,), F32), "k_norm": jnp.zeros((d,), F32),
                "Wo": _w(self, r[1], (h * d, f))}

    def regularizable(self, params):
        return {k: v for k, v in params.items() if k.startswith("W")}

    def apply(self, params, x, *, state, train, rng, mask=None):
        b, t, _ = x.shape
        h, kv, d = self.n_heads, self.n_kv_heads, self.head_dim
        z = ops.dot(x, params["Wqkv"])
        q, g, k, v = jnp.split(z, [h * d, 2 * h * d, (2 * h + kv) * d], axis=-1)

        def heads(a, n):  # [b, t, n d] -> [b, n, t, d]
            return a.reshape(b, t, n, d).transpose(0, 2, 1, 3)

        rot = int(d * self.rotary_fraction)
        q = rotary(rms_norm(heads(q, h), params["q_norm"], self.eps), rot, self.rope_theta)
        k = rotary(rms_norm(heads(k, kv), params["k_norm"], self.eps), rot, self.rope_theta)
        k, v = (jnp.repeat(a, h // kv, axis=1) for a in (k, heads(v, kv)))
        mha = MultiHeadAttention(n_heads=h, causal=True)
        if mha._use_pallas(b, t, d, mask):
            o = mha._flash(q, k, v)
        else:
            o = att.sdpa(q, k, v, mask=mask, causal=True)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, h * d)
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


def _mm(a, b):
    return jnp.matmul(a, b, precision=ops._precision())


def _chunk_step(s, ab):
    """The body of the scan over chunks: S' = A S + B. Emits the state the
    chunk STARTS from."""
    a_i, b_i = ab
    return _mm(a_i, s) + b_i, s


def chunk_gated_delta_rule(q, k, v, g, beta):
    """The gated delta rule in chunks of `CHUNK`. q, k [b, t, h, dk]
    (normalised and scaled by the caller), v [b, t, h, dv], g (log decay,
    <= 0) and beta [b, t, h], all float32 -> o [b, t, h, dv].

    Per head, S_0 = 0 and for every token S <- exp(g) S;
    S <- S + k (beta (v - S^T k))^T; o = S^T q. Within a chunk the writes
    d_j = beta_j (v_j - ...) solve a unit lower-triangular system
    (I + A) D = U - W S_0 with A_jl = beta_j (k_j . k_l) exp(G_j - G_l),
    l < j, G the running sum of g in the chunk; a scan over chunks carries
    S (one [dk, dk] x [dk, dv] product a chunk and head). Everything else is
    batched over all chunks; autodiff through both gives the backward in
    chunks too."""
    chunk, mm = CHUNK, _mm
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = (-t) % chunk
    if pad:  # zero keys write nothing, zero log decay keeps the state
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (g, beta))
    n = (t + pad) // chunk

    def chunks(a):  # [b, T, h, ...] -> [n, b, h, c, ...]
        a = a.reshape((b, n, chunk) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)                                  # [n, b, h, c]
    i = jnp.arange(chunk)
    lower, strict = i[:, None] >= i[None, :], i[:, None] > i[None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, gc[..., :, None] - gc[..., None, :], 0.0)), 0.0)
    kb = k * beta[..., None]
    kt = jnp.swapaxes(k, -1, -2)
    a_mat = jnp.where(strict, mm(kb, kt) * decay, 0.0) + jnp.eye(chunk, dtype=F32)
    rhs = jnp.concatenate([v * beta[..., None], kb * jnp.exp(gc)[..., None]], -1)
    sol = jax.scipy.linalg.solve_triangular(a_mat, rhs, lower=True,
                                            unit_diagonal=True)
    u, w = sol[..., :dv], sol[..., dv:]
    qk = jnp.where(lower, mm(q, kt) * decay, 0.0)
    q_dec = q * jnp.exp(gc)[..., None]
    k_dec_t = jnp.swapaxes(k * jnp.exp(gc[..., -1:] - gc)[..., None], -1, -2)
    # the state across chunks is linear in itself: S' = A S + B with
    # A = exp(G_c) I - K_dec^T W, B = K_dec^T U. A and B come from batched
    # products over all chunks; the scan's body is one product and one sum
    last = jnp.exp(gc[..., -1])[..., None, None]
    a_all = last * jnp.eye(dk, dtype=F32) - mm(k_dec_t, w)
    b_all = mm(k_dec_t, u)

    _, s_all = lax.scan(_chunk_step, jnp.zeros((b, h, dk, dv), F32), (a_all, b_all))
    o = mm(q_dec, s_all) + mm(qk, u - mm(w, s_all))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)             # [b, n, c, h, dv]
    return o.reshape(b, t + pad, h, dv)[:, :t]


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

    def _core(self, params, qkv, z, ba, mask):
        """Everything between the projections, for rows [r, t, ...]: the
        short convolution, the decays, the delta rule, the gated norm."""
        r, t, _ = qkv.shape
        hk, hv, dk, dv = self.n_key_heads, self.n_value_heads, self.key_dim, self.value_dim
        key = hk * dk
        cw = self.conv_width
        padded = jnp.pad(qkv.astype(F32), ((0, 0), (cw - 1, 0), (0, 0)))
        qkv = jax.nn.silu(sum(padded[:, j:j + t] * params["conv"][j]
                              for j in range(cw)))
        q, k, v = jnp.split(qkv, [key, 2 * key], axis=-1)

        def l2(a):
            return a * lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

        q = jnp.repeat(l2(q.reshape(r, t, hk, dk)) * dk ** -0.5, hv // hk, axis=2)
        k = jnp.repeat(l2(k.reshape(r, t, hk, dk)), hv // hk, axis=2)
        ba = ba.astype(F32)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(params["A_log"]) * jax.nn.softplus(ba[..., hv:] + params["dt_bias"])
        if mask is not None:  # a padded token writes nothing, keeps the state
            beta, g = beta * mask[..., None], g * mask[..., None]
        o = chunk_gated_delta_rule(q, k, v.reshape(r, t, hv, dv), g, beta)
        o = rms_norm(o, params["norm"], self.eps, zero_centered=False)
        return (o.reshape(r, t, hv * dv) * jax.nn.silu(z.astype(F32))).astype(z.dtype)

    #: float32 bytes of convolution input the core takes at a time: beyond
    #: it the rows are mapped, each a checkpoint, so that the delta rule's
    #: working set (some 13 arrays of that size) is one group's, not the
    #: batch's (2 x 8192 tokens x 8192 channels would hold 7 GB at once)
    CORE_BYTES = 2 ** 28

    def apply(self, params, x, *, state, train, rng, mask=None):
        b, t, _ = x.shape
        val = self.n_value_heads * self.value_dim
        width = 2 * self.n_key_heads * self.key_dim + val
        qkvz = ops.dot(x, params["Wqkvz"])
        qkv, z = qkvz[..., :width], qkvz[..., width:]
        ba = ops.dot(x, params["Wba"])
        m = None if mask is None else mask.astype(F32)
        if m is not None:  # a padded token enters no convolution window
            qkv = qkv * m[..., None].astype(qkv.dtype)
        core = {k: params[k] for k in ("conv", "A_log", "dt_bias", "norm")}
        rows = min(b, max(1, self.CORE_BYTES // (t * width * 4)))
        while b % rows:        # groups of equal size
            rows -= 1
        if rows == b:
            y = self._core(core, qkv, z, ba, m)
        else:
            def group(a):
                return a.reshape((b // rows, rows) + a.shape[1:])

            args = (qkv, z, ba) + (() if m is None else (m,))
            y = lax.map(jax.checkpoint(lambda a: self._core(core, *a, *[None] * (4 - len(a)))),
                        tuple(group(a) for a in args))
            # kept by the block's 'full' remat: the groups rerun in their
            # own backward and need not run in the block's recompute too
            y = checkpoint_name(y.reshape(b, t, val), REMAT_KEEP)
        y = ops.dot(y, params["Wout"])
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, state


# ---------------------------------------------------------------------------
# routed experts
# ---------------------------------------------------------------------------
def _swiglu(x, wgu, wd):
    gate, up = jnp.split(ops.dot(x, wgu), 2, axis=-1)
    return ops.dot(jax.nn.silu(gate) * up, wd)


def _grouped(x, w, sizes):
    """Rows of x, sorted by group, times their group's matrix."""
    x, w = ops._mixed_cast(x, w)
    return lax.ragged_dot(x, w, sizes, precision=ops._precision())


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
    """SwiGLU experts behind a softmax router, for a rank that holds
    `experts_held` = (first, count) of `n_experts` (default: all), plus a
    gated shared expert. The router scores all `n_experts`, keeps the
    `top_k` largest and renormalises them over the chosen wherever they
    live; this layer adds the terms of its own experts and leaves the
    others' out (on one chip it runs without the exchange that would bring
    other ranks' tokens).

    Device work is a function of shapes alone: the (token, expert)
    assignments of the held experts are sorted by expert into a buffer of
    `capacity_factor` x the expected count (rows x top_k x count /
    n_experts), `lax.ragged_dot` runs over the whole buffer (the padding
    belongs to the last group and is computed), and the rows are gathered
    back weighted. Assignments beyond the buffer are dropped and counted.

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

    def held(self):
        return tuple(self.experts_held) if self.experts_held else (0, self.n_experts)

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
        e, s = self.expert_width, self.shared_width
        r = jax.random.split(rng, 6)
        return {"router": _w(self, r[0], (f, self.n_experts)),
                "Wgu": _w(self, r[1], (count, f, 2 * e)),
                "Wd": _w(self, r[2], (count, e, f)),
                "shared_Wgu": _w(self, r[3], (f, 2 * s)),
                "shared_Wd": _w(self, r[4], (s, f)),
                "shared_gate": _w(self, r[5], (f, 1))}

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
        xs = _to_buffer(xf, order, inv, cap)
        gate, up = jnp.split(_grouped(xs, params["Wgu"], sizes), 2, axis=-1)
        ys = _grouped(jax.nn.silu(gate) * up, params["Wd"], sizes)
        # a slot counts when its expert is held and its position is inside the
        # buffer; the rows of the others (the last group's padding) weigh 0
        kept = (key < count) & (inv < cap)
        out = _from_buffer(ys, jnp.where(kept, top.T.reshape(-1), 0.0).reshape(k, n),
                           order, inv)
        load = (starts[1:] - starts[:-1]).astype(jnp.int32)
        dropped = jnp.maximum(starts[-1] - cap, 0).astype(jnp.int32)
        return out, load, dropped

    def apply(self, params, x, *, state, train, rng, mask=None):
        shape = x.shape
        xf = x.reshape(-1, shape[-1])
        top, idx = self.route(params, xf)
        out, load, dropped = self.routed(params, xf, top, idx)
        gate = jax.nn.sigmoid(ops.dot(xf, params["shared_gate"]).astype(F32))
        shared = _swiglu(xf, params["shared_Wgu"], params["shared_Wd"])
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
