"""Hybrid decoder layers: RMS norm, rotary positions, gated softmax
attention with grouped key/value heads, gated-delta-rule linear attention
with one decay a head or one a key channel, latent attention, routed
experts that are told which experts they hold and a dense gated
feed-forward (ROADMAP R3, R4, R5, R8). The residual blocks that wrap them
are `blocks.py`'s.

All BTF [batch, time, features] like `attention.py`; weights [n_in, n_out],
bias-free. Under the mixed policy the projections run on bf16 operands
(`ops.dot`); norms, the softmax over experts, decays and the delta rule's
state are float32.

  RMSNorm        y = x rsqrt(mean x^2 + eps) (1 + w)   (zero-centred weight;
                 or the plain form .. w)
  rotary         on `rotary_dim` features of a head from an offset on,
                 half-split or interleaved pairing: ONE XLA form for both,
                 y = x cos + (x S) sin over the whole width, S the 0 / 1
                 matrix of the pairing (nothing sliced or concatenated)
  GatedAttention [q | g | k | v] = x Wqkv; per-head RMS norm of q and k;
                 partial rotary; each key/value head repeated to its query
                 heads; causal softmax through `ops.attention.attend`
                 (the flash kernels where its rule admits them);
                 o sigmoid(g) Wo. Gate, norms and positions can each be
                 left out (plain grouped-query attention); the norms'
                 weights zero-centred (1 + w) or plain (w). Where the
                 projection is [q | k | v] with no norm in front, the split
                 into heads and the rotation of q and k are ONE pass over its
                 columns (`ops.attention.rope_heads`: the kernel pair
                 `dl4j_rope_fwd` / `dl4j_rope_bwd` where its rule admits the
                 shapes — a TPU, heads of whole lane tiles); `rotary` behind
                 the transpose to heads everywhere else. Three things a
                 layer may have that its neighbour in the same stack has
                 not: a WINDOW — a query sees the `window` keys up to and
                 with its own (512 keys: the query's own among them, 511
                 back), the flash kernels visit that band's blocks alone —,
                 a gate a HEAD (`gate="head"`: one sigmoid(x Wg) a head and
                 token, `Wg` a leaf of its own) in place of one a feature,
                 and a frequency SCHEDULE for its rotation (`rope_scaling`,
                 `frequencies`: yarn's ramp between the trained and the
                 `factor` times slower pairs, cos and sin times its factor)
  GatedDeltaNet  [q | k | v | z] = x Wqkvz, [b | a] = x Wba; short causal
                 depthwise convolution + silu over [q | k | v]; the gated
                 delta rule S <- exp(g) S; S += k (beta (v - S^T k))^T;
                 o = S^T q, run in chunks (`ops.delta.gdn_chunks`: a
                 kernel pair on a TPU; `chunk_gated_delta_rule` elsewhere);
                 gated RMS norm by silu(z); Wout. Between the projections
                 everything is chunk-major [n, b, heads, c, d]: re-tiled
                 once in (`to_chunks`) and once out (`from_chunks`), in
                 the projection's dtype; q and k keep their key heads
  KimiDeltaAttention  the delta rule with a decay of its own a key CHANNEL
                 (KDA): S <- Diag(exp(g)) S, g through a low-rank
                 bottleneck; `chunk_channel_gated_delta_rule`, whose chunk
                 scores are exact and finite at any decay (`_decayed`) and
                 whose solve, scan and read-out are the scalar rule's;
                 per-head RMS norm gated by a sigmoid through a second
                 bottleneck; Wo
  LatentAttention  keys and values through a normalised bottleneck plus one
                 key part shared by all heads (MLA); rotary positions on
                 that part and on the queries' matching part alone, or no
                 positions; keys wider than values through
                 `ops.attention.attend`
  GatedShortConv [B | C | z] = x Win; y = (C conv(B z)) Wout, conv a short
                 causal depthwise convolution with no activation: a mixer
                 with no recurrence and no state beyond its last taps
  GatedMLP       act(x W1) Wd with an expert's non-linearity, dense
  RoutedExperts  a router over ALL experts (top-k of a softmax; or sigmoid
                 scores, chosen by score + a selection bias, weighted by
                 the bare scores), top-k renormalised; the terms of the
                 experts HELD (`experts_held` = first, count) through a
                 sorted buffer of static capacity and XLA's grouped
                 product (`ops.linear.grouped_dot`, which pads widths);
                 SwiGLU or relu^2 experts; a shared expert, gated or not,
                 or none. With an `exchange_axis`: ALL experts, split over
                 the mesh axis's ranks, tokens exchanged with the ranks that
                 hold their experts (`lax.all_to_all` out and back inside a
                 `shard_map` island; pair buffers of `capacity_factor` x the
                 load).
                 Its device work is a function of shapes alone; overflow is
                 counted and left out. Counters live in the layer's state
                 (`counters`) and reach `telemetry.fit_log()` once a fit.

`to_chunks`, `conv_silu` (on a TPU the kernel pair `dl4j_convsilu_fwd` /
`dl4j_convsilu_bwd` behind `ops.delta.conv_silu_chunks`, wherever its rule
admits the operands; XLA elsewhere), `from_chunks`, the row mapping
(`rows_at_a_time`, `over_row_groups`) and the decay counters
(`decay_counters`, ..) also serve the state-space mixer of `ssm.py`.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from deeplearning4j_tpu.nn import initializers as init_mod
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.layers.base import REMAT_KEEP, Layer, register_layer
from deeplearning4j_tpu.ops import attention as att
from deeplearning4j_tpu.ops import delta
from deeplearning4j_tpu.ops import linear as ops
from deeplearning4j_tpu.ops.rope_kernels import frequencies  # noqa: F401 — `rotary`'s schedule
from deeplearning4j_tpu.telemetry.trace import device_scope

F32 = jnp.float32


def _w(layer: Layer, rng, shape):
    return init_mod.init(layer.weight_init or "xavier", rng, shape,
                         fan_in=shape[-2], fan_out=shape[-1])


def rms_norm(x, w, eps: float, zero_centered: bool = True):
    """RMS norm over the last axis, computed in float32, in x's dtype."""
    xf = x.astype(F32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w if zero_centered else w)).astype(x.dtype)


def rotary(x, rotary_dim: int, theta: float, start: int = 0, interleave: bool = False,
           scaling: Optional[dict] = None):
    """Rotary positions on the `rotary_dim` features of x [b, h, t, d] from
    `start` on; the rest passes through. Pair j turns by the angle
    pos theta^(-2j/rotary_dim), pos the index in the sequence, computed in
    float32 — or, with a `scaling` (a published `rope_parameters` entry), by
    pos f_j of its schedule, cos and sin times its factor (`frequencies`: the
    turned part is scaled, the rest is not). Half-split: feature j of the part
    pairs with j + rotary_dim/2; `interleave`: feature 2j with 2j + 1.

    Either pairing is y = x cos + swap(x) sin over the WHOLE width, cos 1 and
    sin 0 outside the part, swap(x) = x S with S the 0 / 1 matrix that puts
    each feature's partner in its place — one MXU product, exact in any dtype
    (a column selects one element), in place of slicing the part out, moving
    its halves (or rolling it by a lane both ways) and concatenating it back:
    on a v5e the sliced form takes 1.1 - 1.7 x this one's time forward and
    1.2 - 2.5 x backward at every head width measured (PERF.md section 6,
    PR 46; PR 38 for the neighbours). Where an attention layer's projection
    needs no norm in front, `ops.attention.rope_heads` does the half-split
    rotation and the head split in one kernel pass instead."""
    t, width = x.shape[2], x.shape[3]
    lane, half = np.arange(width) - start, rotary_dim // 2
    inside = (lane >= 0) & (lane < rotary_dim)
    # the first of a pair takes -sin of the second, `away` lanes further on
    first, away, pair = ((lane % 2 == 0, 1, lane // 2) if interleave
                         else (lane < half, half, lane % half))
    swap = np.zeros((width, width), np.float32)
    at = np.arange(width)[inside]
    swap[at + np.where(first[inside], away, -away), at] = 1.0    # y[i] takes x[i + away] or x[i - away]
    pair = jnp.asarray(np.where(inside, pair, 0), F32)
    pos = jnp.arange(t, dtype=F32)[:, None]
    freq, scale = frequencies(rotary_dim, theta, scaling, pair)
    ang = pos * freq

    def scaled(a):   # a schedule's factor on both tables; none: the operations as they were
        return a if scale == 1.0 else a * scale

    cos = jnp.where(inside, scaled(jnp.cos(ang)), 1.0)
    sin = jnp.where(inside, jnp.where(first, -scaled(jnp.sin(ang)), scaled(jnp.sin(ang))), 0.0)
    partner = jnp.einsum("bhtd,de->bhte", x, jnp.asarray(swap, x.dtype),
                         precision=lax.Precision.HIGHEST, preferred_element_type=F32)
    return (x.astype(F32) * cos + partner * sin).astype(x.dtype)


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
    grouped-query attention that knows no positions. The norms multiply by
    1 + w, w from zero (`qk_norm_zero_centered`), or by w from one.

    `gate` says what a gate weighs: "element" (the [q | g | ..] columns
    above, one gate a feature of every head) or "head" — ONE scalar a head
    and token, sigmoid(x Wg) with a leaf `Wg` [f, n_heads] of its own, times
    the head's output before `Wo`; Wqkv then stays [q | k | v]. `window`: a
    query sees the `window` keys up to and with its own (512: 511 back) and
    the flash kernels visit that band's blocks alone; None: the whole past.
    `rope_scaling`: a published `rope_parameters` entry (`rope_type` "yarn",
    `factor`, `original_max_position_embeddings`, `beta_fast`, `beta_slow`,
    `attention_factor`) — the frequency schedule and the factor on cos and
    sin of `frequencies`; None: theta^(-2j/rot).

    A windowed layer keeps `counters` in its state (`steps`, and the keys a
    query has inside its band / in the blocks the kernels' plan visits,
    summed over steps): `telemetry.fit_log()` reports them under `attention`
    with the window and the head counts (`counter_summary`)."""

    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    rotary_fraction: float = 0.25
    rope_theta: float = 1e7
    eps: float = 1e-6
    gated: bool = True
    qk_norm: bool = True
    qk_norm_zero_centered: bool = True
    gate: str = "element"
    window: Optional[int] = None
    rope_scaling: Optional[dict] = None

    def output_type(self, input_type):
        return input_type

    def _gate(self):
        """None | "element" | "head"."""
        if self.gate not in ("element", "head"):
            raise ValueError(f"gate={self.gate!r}: 'element' or 'head'")
        return self.gate if self.gated else None

    def init_params(self, rng, input_type):
        f = input_type.size
        h, kv, d = self.n_heads, self.n_kv_heads, self.head_dim
        if h % kv:
            raise ValueError(f"n_kv_heads={kv} must divide n_heads={h}")
        gate = self._gate()
        r = jax.random.split(rng, 3 if gate == "head" else 2)
        p = {"Wqkv": _w(self, r[0], (f, ((2 if gate == "element" else 1) * h + 2 * kv) * d)),
             "Wo": _w(self, r[1], (h * d, f))}
        if gate == "head":
            p["Wg"] = _w(self, r[2], (f, h))
        if self.qk_norm:
            start = jnp.zeros if self.qk_norm_zero_centered else jnp.ones
            p.update(q_norm=start((d,), F32), k_norm=start((d,), F32))
        return p

    def init_state(self, input_type):
        if self.window is None:
            return {}
        return {"counters": {"steps": jnp.zeros((), jnp.int32),
                             "band_keys": jnp.zeros((), F32),
                             "visited_keys": jnp.zeros((), F32)}}

    def counter_summary(self, added):
        """A windowed layer's plan over a fit, under `attention`: the keys a
        query has inside its band and in the blocks visited for it (means over
        the sequence and the steps), and the first over the second."""
        steps = max(int(added["steps"][0]), 1)
        band, visited = (float(added[k][0]) / steps for k in ("band_keys", "visited_keys"))
        return "attention", {
            "steps": int(added["steps"][0]), "window": self.window,
            "n_heads": self.n_heads, "n_kv_heads": self.n_kv_heads,
            "band_keys_per_query": band, "visited_keys_per_query": visited,
            "band_fill": band / visited if visited else 0.0}

    def regularizable(self, params):
        return {k: v for k, v in params.items() if k.startswith("W")}

    def apply(self, params, x, *, state, train, rng, mask=None):
        b, t, _ = x.shape
        h, kv, d = self.n_heads, self.n_kv_heads, self.head_dim
        rot, theta = int(d * self.rotary_fraction), self.rope_theta
        gate = self._gate()
        with device_scope("proj"):
            z = ops.dot(x, params["Wqkv"])

        def heads(a, n):  # [b, t, n d] -> [b, n, t, d]
            return a.reshape(b, t, n, d).transpose(0, 2, 1, 3)

        split = None
        if rot and not (gate == "element" or self.qk_norm):
            # [q | k | v] as it leaves the product: ONE pass splits the heads
            # and turns q's and k's, and its backward writes dz whole. Behind a
            # norm the pass would read an array XLA otherwise never writes
            # (norm, transpose and rotation are one fusion): slower, measured
            with device_scope("rope"):
                split = att.rope_heads(z, (h, kv, kv), (True, True, False), d, rot, theta,
                                       scaling=self.rope_scaling)
        if split is not None:
            q, k, v = split
        else:
            if gate == "element":
                q, g, k, v = jnp.split(z, [h * d, 2 * h * d, (2 * h + kv) * d], axis=-1)
            else:
                q, k, v = jnp.split(z, [h * d, (h + kv) * d], axis=-1)

            def prepared(a, n, norm):  # a head's norm, then its positions
                with device_scope("gates"):
                    a = heads(a, n)
                    if self.qk_norm:
                        a = rms_norm(a, params[norm], self.eps, self.qk_norm_zero_centered)
                if not rot:
                    return a
                with device_scope("rope"):
                    return rotary(a, rot, theta, scaling=self.rope_scaling)

            q, k = prepared(q, h, "q_norm"), prepared(k, kv, "k_norm")
            with device_scope("gates"):
                v = heads(v, kv)
        with device_scope("gates"):
            k, v = (jnp.repeat(a, h // kv, axis=1) for a in (k, v))
        with device_scope("attend"):
            o = att.attend(q, k, v, causal=True, mask=mask, window=self.window)
        if gate == "head":
            with device_scope("gates"):
                # one scalar a head and token, on [b, h, t, d] before the transpose back
                gh = jax.nn.sigmoid(ops.dot(x, params["Wg"]).astype(F32))
                o = o * gh.transpose(0, 2, 1)[..., None].astype(o.dtype)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, h * d)
        if gate == "element":
            with device_scope("gates"):
                o = o * jax.nn.sigmoid(g.astype(F32)).astype(o.dtype)
        with device_scope("out"):
            y = ops.dot(o, params["Wo"])
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        if train and self.window is not None:
            with device_scope("counters"):
                band, visited = att.band_fill(t, d, q.dtype, self.window)
                c = state["counters"]
                state = {"counters": {"steps": c["steps"] + 1,
                                      "band_keys": c["band_keys"] + band / t,
                                      "visited_keys": c["visited_keys"] + visited / t}}
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


def l2_normalised(a):
    """a / |a| over the last axis (1e-6 under the root): the delta rules'
    q and k."""
    return a * lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)


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
    with device_scope("solve"):
        u, w = _solve_writes(a_mat, rhs, dv)
    qk = jnp.where(lower, qk * decay, 0.0)
    q_dec = q * jnp.exp(gc)[..., None]
    k_dec = k * jnp.exp(gc[..., -1:] - gc)[..., None]
    last = jnp.exp(gc[..., -1])[..., None, None]
    with device_scope("scan"):
        o, _ = _scan_read(u, w, qk, q_dec, k_dec, last * jnp.eye(dk, dtype=F32))
    return o.reshape(n, b, hv, c, dv)


# What every chunked delta rule does once its chunk's scores are made, the
# decay one scalar a head and token or a vector over the key channels alike.
def _solve_writes(a_mat, rhs, dv: int):
    """The chunk's writes: (I + A) [U | -W] = rhs, unit lower-triangular;
    a_mat [n, b, *heads, c, c], rhs [.., c, dv + dk] -> (U [.., c, dv],
    -W [.., c, dk])."""
    sol = jax.scipy.linalg.solve_triangular(a_mat, rhs, lower=True,
                                            unit_diagonal=True)
    return sol[..., :dv], sol[..., dv:]


def _scan_read(u, w, qk, q_dec, k_dec, a_diag):
    """Carry the state across chunks and read it: u, w (= -W) from
    `_solve_writes`, qk the decayed Q K^T [.., c, c], q_dec (q times the
    decay since the chunk's start) and k_dec (k times the decay up to its
    end) [.., c, dk], a_diag the state's own decay over the chunk as a
    matrix [.., dk, dk] -> (o [.., c, dv], the states the chunks start from
    [n, b, heads, dk, dv]).

    The state across chunks is linear in itself: S' = A S + B with
    A = a_diag - K_dec^T W, B = K_dec^T U. A and B come from batched
    products over all chunks; the scan's body is one product and one sum."""
    mm = _mm
    dk, dv = w.shape[-1], u.shape[-1]
    a_all = a_diag + mm("...ci,...cj->...ij", k_dec, w)
    b_all = mm("...ci,...cv->...iv", k_dec, u)
    n, b = a_all.shape[:2]
    heads = a_all.shape[2:-2]
    hv = math.prod(heads)
    _, s_all = lax.scan(_chunk_step, jnp.zeros((b, hv, dk, dv), F32),
                        (a_all.reshape(n, b, hv, dk, dk), b_all.reshape(n, b, hv, dk, dv)))
    s_in = s_all.reshape((n, b) + heads + (dk, dv))
    o = mm("...ck,...kv->...cv", q_dec, s_in) + mm(
        "...ij,...jv->...iv", qk, u + mm("...ck,...kv->...cv", w, s_in))
    return o, s_all


#: tokens a sub-block of a chunk whose decayed terms are formed directly
#: (`_decayed`)
SUB = 16


def _pair_decay(gc):
    """E_jld = exp(G_jd - G_ld) for l <= j, 0 above the diagonal: gc
    [.., s, d] -> [.., s, s, d]. The exponent is <= 0 wherever it counts."""
    s = gc.shape[-2]
    i = jnp.arange(s)
    lower = (i[:, None] >= i[None, :])[..., None]
    return jnp.where(lower, jnp.exp(jnp.where(
        lower, gc[..., :, None, :] - gc[..., None, :, :], 0.0)), 0.0)


def _halves(x):
    """[.., c, m] -> [.., 2, c / 2, m]: the earlier and the later half."""
    return x.reshape(x.shape[:-2] + (2, x.shape[-2] // 2, x.shape[-1]))


def _decayed(what: str, x, y, gc):
    """One of three sums over the pairs l <= j of a chunk's tokens, each
    term carrying E_jld = exp(G_jd - G_ld), gc = G the running log decay
    [.., c, d], falling along the tokens:

      "scores"  x = a, y = k [.., c, d] -> P_jl = sum_d a_jd k_ld E_jld
                                           [.., c, c], 0 above the diagonal
      "rows"    x = W [.., c, c], y = k -> sum_{l <= j} W_jl k_ld E_jld  [.., c, d]
      "cols"    x = W, y = a            -> sum_{j >= l} W_jl a_jd E_jld  [.., c, d]

    With a decay of its own a channel the exponent sits INSIDE the
    contraction, and the factorisation (a exp(G)) (k exp(-G))^T overflows
    float32 once a channel has decayed by e^-88 inside the chunk. Exact and
    finite for ANY decay instead, by halves: the later half's rows against
    the earlier half's are referred to the later half's FIRST row r,

        E_jld = exp(G_jd - r_d) exp(r_d - G_ld),   l < first <= j

    both exponents <= 0 (a factor that underflows to 0 stands for a product
    that is smaller still), one product on the MXU; the two diagonal blocks
    the same way again, down to `SUB` tokens, where the [s, s, d] terms are
    formed and summed as they are."""
    c = gc.shape[-2]
    if c <= SUB:
        e = _pair_decay(gc)
        if what == "scores":
            return jnp.sum(x[..., :, None, :] * y[..., None, :, :] * e, axis=-1)
        if what == "rows":
            return jnp.sum(x[..., :, :, None] * y[..., None, :, :] * e, axis=-2)
        return jnp.sum(x[..., :, :, None] * y[..., :, None, :] * e, axis=-3)
    p = c // 2
    g2, y2 = _halves(gc), _halves(y)
    r = g2[..., 1, :1, :]                                          # the later half's first row
    later, earlier = jnp.exp(g2[..., 1, :, :] - r), jnp.exp(r - g2[..., 0, :, :])
    if what == "scores":
        x2 = _halves(x)
        diag = _decayed(what, x2, y2, g2)                          # [.., 2, p, p]
        off = _mm("...id,...jd->...ij", x2[..., 1, :, :] * later, y2[..., 0, :, :] * earlier)
        return jnp.concatenate([
            jnp.concatenate([diag[..., 0, :, :], jnp.zeros_like(off)], -1),
            jnp.concatenate([off, diag[..., 1, :, :]], -1)], -2)
    # the weights' two diagonal blocks [.., 2, p, p] and the block below them
    w = x.reshape(x.shape[:-2] + (2, p, 2, p))
    diag = _decayed(what, jnp.stack([w[..., 0, :, 0, :], w[..., 1, :, 1, :]], -3), y2, g2)
    off = w[..., 1, :, 0, :]                                       # rows later, columns earlier
    zero = jnp.zeros_like(diag[..., 0, :, :])
    if what == "rows":
        cross = later * _mm("...ij,...jd->...id", off, y2[..., 0, :, :] * earlier)
        return (diag + jnp.stack([zero, cross], -3)).reshape(gc.shape)
    cross = earlier * _mm("...ij,...id->...jd", off, y2[..., 1, :, :] * later)
    return (diag + jnp.stack([cross, zero], -3)).reshape(gc.shape)


@jax.custom_vjp
def _decayed_scores(a, k, gc):
    """P_jl = sum_d a_jd k_ld exp(G_jd - G_ld) for l <= j, 0 above the
    diagonal: a, k, gc [.., c, d] -> [.., c, c] (`_decayed`). The backward
    is written out: da and dk are the two weighted sums of the same pairs,
    and since every term is a_jd k_ld E_jld, dG = a da - k dk — no third
    and fourth pass over the pairs, and nothing kept but a, k and G."""
    return _decayed("scores", a, k, gc)


def _decayed_scores_fwd(a, k, gc):
    return _decayed("scores", a, k, gc), (a, k, gc)


def _decayed_scores_bwd(res, dp):
    a, k, gc = res
    da = _decayed("rows", dp, k, gc)
    dk = _decayed("cols", dp, a, gc)
    return da, dk, a * da - k * dk


_decayed_scores.defvjp(_decayed_scores_fwd, _decayed_scores_bwd)


def chunk_channel_gated_delta_rule(q, k, v, g, beta):
    """The delta rule with a decay of its own a key CHANNEL (KDA), over
    chunks of `CHUNK` tokens, chunk-major (`to_chunks`): q, k [n, b, h, c,
    dk] (normalised and scaled by the caller), v [n, b, h, c, dv], g (log
    decay, <= 0) [n, b, h, c, dk], beta [n, b, h, c], all float32 ->
    (o [n, b, h, c, dv], the states the chunks start from [n, b, h, dk, dv]).

    Per head, S_0 = 0 and for every token S <- Diag(exp(g)) S;
    S <- S + k (beta (v - S^T k))^T; o = S^T q. Within a chunk the writes
    solve (I + A) D = U - W S_0 as in `chunk_gated_delta_rule`, with
    A_jl = beta_j sum_d k_jd k_ld exp(G_jd - G_ld), l < j: the decay no
    longer factors out of K K^T, so A and the read-out's Q K^T come from
    `_decayed_scores`; every other decay is a product with exp of a
    non-positive number (since the chunk's start, or up to its end); the
    solve, the scan over chunks and the read-out are `_solve_writes` and
    `_scan_read`, shared with the scalar rule. The backward is autodiff
    through all of it but the decayed scores, whose own is written out; in
    chunks, and as finite as the forward."""
    c, dk = q.shape[-2:]
    dv = v.shape[-1]
    gc = jnp.cumsum(g, axis=-2)
    i = jnp.arange(c)
    strict = i[:, None] > i[None, :]
    a_mat = jnp.where(strict, _decayed_scores(k, k, gc) * beta[..., None], 0.0) \
        + jnp.eye(c, dtype=F32)
    since, last = jnp.exp(gc), gc[..., -1:, :]
    rhs = jnp.concatenate([v * beta[..., None], k * since * -beta[..., None]], -1)
    with device_scope("solve"):
        u, w = _solve_writes(a_mat, rhs, dv)
    a_diag = jnp.exp(last)[..., 0, :, None] * jnp.eye(dk, dtype=F32)
    qk = _decayed_scores(q, k, gc)
    with device_scope("scan"):
        return _scan_read(u, w, qk, q * since, k * jnp.exp(last - gc), a_diag)


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
    the bf16 projection under the mixed policy). On a TPU it is the kernel
    pair `dl4j_convsilu_fwd` / `dl4j_convsilu_bwd` wherever
    `ops.delta.conv_silu_impl` admits the operands (one pass over the bytes
    a direction, no `pre` in HBM); the XLA form `_conv_silu` everywhere
    else, and the tests' oracle."""
    y = delta.conv_silu_chunks(x, w, b)
    return _conv_silu(x, w, b) if y is None else y


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


def _keep(tree):
    """`tree`'s arrays tagged `REMAT_KEEP`: a block's 'full' remat keeps them
    (`util/jaxcompat.py` has the rule); anywhere else the tag lowers to nothing."""
    return jax.tree_util.tree_map(lambda a: checkpoint_name(a, REMAT_KEEP), tree)


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
    with device_scope("retile"):
        args = [a.reshape((b // rows, rows, t) + (tuple(heads) or a.shape[2:]))
                for a, heads in arrays]
        args = tuple(jax.vmap(lambda g: to_chunks(g, chunk))(a) for a in args)
    if rows == b:
        return core(*(a[0] for a in args))
    out = lax.map(jax.checkpoint(lambda a: core(*a)), args)
    return _keep(out)


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
        with device_scope("conv"):
            qk = conv_silu(qk, params["conv"][:, :2 * key].reshape(cw, 2 * hk, 1, -1))
            v = conv_silu(v, params["conv"][:, 2 * key:].reshape(cw, hv, 1, -1))

        with device_scope("gates"):
            ba = ba.astype(F32)
            beta = jax.nn.sigmoid(ba[:, :, :hv])
            g = -jnp.exp(params["A_log"])[:, None] * jax.nn.softplus(
                ba[:, :, hv:] + params["dt_bias"][:, None])
            if mask is not None:  # a padded token writes nothing, keeps the state
                beta, g = beta * mask, g * mask
            q = l2_normalised(qk[:, :, :hk]) * self.key_dim ** -0.5
            k = l2_normalised(qk[:, :, hk:])
        with device_scope("rule"):
            # the kernel pair where `ops.delta.gdn_impl` admits it, else the XLA form
            o = delta.gdn_chunks(q, k, v, g, beta)
            if o is None:
                o = chunk_gated_delta_rule(q, k, v, g, beta)
        with device_scope("norm_gate"):
            o = rms_norm(o, params["norm"], self.eps, zero_centered=False)
            o = (o * jax.nn.silu(z.astype(F32))).astype(z.dtype)
        with device_scope("retile"):
            y = from_chunks(o, t)
            return y.reshape(y.shape[:2] + (-1,))

    #: `CORE_BYTES` for this layer. At 8192 tokens x 8192 channels a row the
    #: convolution input is 268 MB, and so are q with k, v, the output and the
    #: cotangent of each. With the chunk rule as kernels a row's backward
    #: holds besides them the chunk-start states (268 MB) and what the rerun
    #: keeps a chunk (scores 134, inverse 67, [U | W] 268): 2.06 GB a row
    #: while its backward runs, by `memory_analysis()` of one layer alone at
    #: [1, 8192, 2048] for a described v5e. The XLA form takes 3.28 GB: the
    #: solve's right-hand side and solution and the scan's A, B and S are
    #: 268 MB each, and so are their cotangents
    CORE_BYTES = CORE_BYTES

    def apply(self, params, x, *, state, train, rng, mask=None):
        b, t, _ = x.shape
        hk, hv, dk, dv = self.n_key_heads, self.n_value_heads, self.key_dim, self.value_dim
        key, val = hk * dk, hv * dv
        with device_scope("proj"):
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
        with device_scope("proj"):
            y = ops.dot(y.reshape(b, t, val), params["Wout"])
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, state


# ---------------------------------------------------------------------------
# counters of a recurrence's decay and state, shared by the mixers that keep them
# ---------------------------------------------------------------------------
def decay_counters():
    """The state a recurrent mixer counts in: `steps` and float32 sums over
    the steps of the mean and of the smallest per-token decay and of the
    largest |state| a chunk starts from."""
    zero = lambda dtype: jnp.zeros((), dtype)  # noqa: E731 — a buffer each: state is donated
    return {"counters": {"steps": zero(jnp.int32), "decay_sum": zero(F32),
                         "decay_min_sum": zero(F32), "state_max_sum": zero(F32)}}


def count_decay(state, mean, low, high):
    """`decay_counters` after one more step whose row groups read `mean`,
    `low`, `high` (a scalar, or one a group)."""
    c = state["counters"]
    return {"counters": {
        "steps": c["steps"] + 1, "decay_sum": c["decay_sum"] + jnp.mean(mean),
        "decay_min_sum": c["decay_min_sum"] + jnp.min(low),
        "state_max_sum": c["state_max_sum"] + jnp.max(high)}}


def decay_summary(key: str, added):
    """Per-step means of `decay_counters` over a fit, under `key`."""
    steps = max(int(added["steps"][0]), 1)
    return key, {
        "steps": int(added["steps"][0]),
        "decay_mean": float(added["decay_sum"][0]) / steps,
        "decay_min": float(added["decay_min_sum"][0]) / steps,
        "state_abs_max": float(added["state_max_sum"][0]) / steps,
    }


def decay_stats(decay, states):
    """(mean, smallest) of the per-token decays and the largest |state|."""
    decay, states = lax.stop_gradient(decay), lax.stop_gradient(states)
    return jnp.mean(decay), jnp.min(decay), jnp.max(jnp.abs(states))


@register_layer
@dataclass
class KimiDeltaAttention(Layer):
    """Delta-rule linear attention whose decay is a vector over a head's
    key channels (KDA) over [b, t, f]; n_heads heads, keys and values
    head_dim wide. Wqkv [f, 3 n_heads head_dim] = [q | k | v] with a short
    causal depthwise convolution + silu over all three (no bias); Wlow
    [f, 2 head_dim + n_heads] = [fa | ga | b]: the decay and the output
    gate each through a bottleneck as wide as a head, beta a head;

      q = l2(q) head_dim^-0.5, k = l2(k), beta = sigmoid(x Wb)
      g = -exp(A_log[h]) softplus(x Wfa Wfb + dt_bias)      [n_heads x head_dim]
      S <- Diag(exp(g)) S;  S <- S + k (beta (v - S^T k))^T;  o = S^T q
      y = (rms(o; norm) sigmoid(x Wga Wgb)) Wo,  the norm over a head

    run in chunks (`chunk_channel_gated_delta_rule`), chunk-major between
    the projections like `GatedDeltaNet`. `A_log` one a head, `dt_bias`
    one a channel. State `counters` (`decay_counters`; `telemetry.fit_log()`
    reports them under `kda`): the decay's mean and its smallest value over
    tokens and channels, the largest |state| a chunk starts from."""

    n_heads: int = 32
    head_dim: int = 128
    conv_width: int = 4
    eps: float = 1e-5

    #: `CORE_BYTES` for this layer: at 8192 tokens x 12288 convolved
    #: channels a row is 403 MB, so the rows are mapped one at a time
    CORE_BYTES = CORE_BYTES

    def output_type(self, input_type):
        return input_type

    def init_params(self, rng, input_type):
        f = input_type.size
        h, d = self.n_heads, self.head_dim
        r = jax.random.split(rng, 8)
        # dt log-uniform over [1e-3, 0.1], dt_bias its inverse softplus; A ~ U(1, 16)
        dt = jnp.exp(jax.random.uniform(r[5], (h * d,), F32) * jnp.log(100.0) + jnp.log(1e-3))
        return {
            "Wqkv": _w(self, r[0], (f, 3 * h * d)),
            "conv": jax.random.uniform(r[1], (self.conv_width, 3 * h * d), F32,
                                       -1.0, 1.0) * self.conv_width ** -0.5,
            "Wlow": _w(self, r[2], (f, 2 * d + h)),
            "Wfb": _w(self, r[3], (d, h * d)),
            "Wgb": _w(self, r[4], (d, h * d)),
            "A_log": jnp.log(jax.random.uniform(r[6], (h,), F32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "norm": jnp.ones((d,), F32),
            "Wo": _w(self, r[7], (h * d, f)),
        }

    def init_state(self, input_type):
        return decay_counters()

    def counter_summary(self, added):
        return decay_summary("kda", added)

    def regularizable(self, params):
        return {k: v for k, v in params.items() if k.startswith("W")}

    def _core(self, params, t, qkv, f, ba, z, mask=None):
        """Everything between the projections, for rows r of t tokens.
        Chunk-major (`to_chunks`) and in the projection's dtype: qkv
        [n, r, 3 h, c, d], f (the decay before its softplus) and z (the
        gate before its sigmoid) [n, r, h, c, d], ba [n, r, h, c], mask
        [n, r, 1, c] -> ([r, t, h d], the step's counters)."""
        h, cw = self.n_heads, self.conv_width
        with device_scope("conv"):
            qkv = conv_silu(qkv, params["conv"].reshape(cw, 3 * h, 1, -1))

        with device_scope("gates"):
            beta = jax.nn.sigmoid(ba.astype(F32))
            g = -jnp.exp(params["A_log"])[:, None, None] * jax.nn.softplus(
                f.astype(F32) + params["dt_bias"].reshape(h, 1, -1))
            if mask is not None:  # a padded token writes nothing, keeps the state
                beta, g = beta * mask, g * mask[..., None]
            rule = (l2_normalised(qkv[:, :, :h]) * self.head_dim ** -0.5,
                    l2_normalised(qkv[:, :, h:2 * h]), qkv[:, :, 2 * h:], g, beta)
        with device_scope("rule"):
            # the kernel pair where `ops.delta.kda_impl` admits it, else the XLA form
            o, states = delta.kda_chunks(*rule) or chunk_channel_gated_delta_rule(*rule)
        with device_scope("norm_gate"):
            o = rms_norm(o, params["norm"], self.eps, zero_centered=False)
            o = (o * jax.nn.sigmoid(z.astype(F32))).astype(z.dtype)
        with device_scope("retile"):
            y = from_chunks(o, t)
            y = y.reshape(y.shape[:2] + (-1,))
        with device_scope("counters"):
            return y, decay_stats(jnp.exp(g), states)

    def apply(self, params, x, *, state, train, rng, mask=None):
        b, t, _ = x.shape
        h, d = self.n_heads, self.head_dim
        with device_scope("proj"):
            qkv = ops.dot(x, params["Wqkv"])
            low = ops.dot(x, params["Wlow"])
            f = ops.dot(low[..., :d], params["Wfb"])
            z = ops.dot(low[..., d:2 * d], params["Wgb"])
        if mask is not None:  # a padded token enters no convolution window
            qkv = qkv * mask[..., None].astype(qkv.dtype)
        rows = rows_at_a_time(b, t * 3 * h * d * 4, self.CORE_BYTES)
        args = [(qkv, (3 * h, d)), (f, (h, d)), (low[..., 2 * d:], ()), (z, (h, d))]
        if mask is not None:
            args.append((mask.astype(F32)[..., None], ()))
        core = {k: params[k] for k in ("conv", "A_log", "dt_bias", "norm")}
        y, stats = over_row_groups(lambda *a: self._core(core, t, *a), args, rows)
        with device_scope("proj"):
            y = ops.dot(y.reshape(b, t, h * d), params["Wo"])
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        with device_scope("counters"):
            return y, count_decay(state, *stats) if train else state


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------
@register_layer
@dataclass
class LatentAttention(Layer):
    """Causal softmax attention whose keys and values come through a
    normalised bottleneck (MLA). Wq [f, n_heads (nope_dim + rope_dim)]; Wkva
    [f, kv_rank + rope_dim] = [c | kr]; [k_nope | v] = rms(c; kv_norm) Wkvb,
    n_heads heads of [nope_dim | v_dim]; a head's key is [k_nope | kr], the
    rope_dim-wide part kr ONE for all heads. Positions are DECOUPLED from
    the content: with a `rope_theta` the last rope_dim features of each
    query head and kr — once, before it is broadcast to the heads — are
    rotated (`rotary`, pairs interleaved or half-split by
    `rope_interleave`), the nope parts and the values never; with None the
    layer knows no positions. Scores q k^T (nope_dim + rope_dim)^-0.5
    through `ops.attention.attend`, whose flash kernels take a key width
    that differs from the value width; Wo [n_heads v_dim, f]. No bias.

    q after its rotation, k after the concatenate and v are tagged
    `REMAT_KEEP`: the flash backward only reads them, and to make them a
    second time a block's 'full' remat would run `x Wq`, `c Wkvb`, both
    rotations, the broadcast and the concatenate again (at five layers of
    [2, 32, 8192, 192 | 192 | 128] bfloat16 a 769 ms step fell by 29.6 ms for
    0.57 GB a layer kept: PERF.md section 6, PR 48). `x Wkva` and the norm
    stay in the recompute: the norm's backward reads `c`."""

    n_heads: int = 32
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    eps: float = 1e-5
    rope_theta: Optional[float] = None
    rope_interleave: bool = True

    def output_type(self, input_type):
        return input_type

    def init_params(self, rng, input_type):
        f = input_type.size
        h = self.n_heads
        r = jax.random.split(rng, 4)
        return {"Wq": _w(self, r[0], (f, h * (self.nope_dim + self.rope_dim))),
                "Wkva": _w(self, r[1], (f, self.kv_rank + self.rope_dim)),
                "kv_norm": jnp.ones((self.kv_rank,), F32),
                "Wkvb": _w(self, r[2], (self.kv_rank, h * (self.nope_dim + self.v_dim))),
                "Wo": _w(self, r[3], (h * self.v_dim, f))}

    def regularizable(self, params):
        return {k: v for k, v in params.items() if k.startswith("W")}

    def apply(self, params, x, *, state, train, rng, mask=None):
        b, t, _ = x.shape
        h, nope = self.n_heads, self.nope_dim

        def heads(a):  # [b, t, h d] -> [b, h, t, d]
            return a.reshape(b, t, h, -1).transpose(0, 2, 1, 3)

        with device_scope("proj"):
            q = heads(ops.dot(x, params["Wq"]))
            ckr = ops.dot(x, params["Wkva"])
        with device_scope("gates"):  # the bottleneck's norm
            c = rms_norm(ckr[..., :self.kv_rank], params["kv_norm"], self.eps,
                         zero_centered=False)
        with device_scope("proj"):
            kv = heads(ops.dot(c, params["Wkvb"]))
            kr = ckr[:, None, :, self.kv_rank:]  # [b, 1, t, rope_dim]: one head
        if self.rope_theta is not None:
            with device_scope("rope"):
                q = rotary(q, self.rope_dim, self.rope_theta, nope, self.rope_interleave)
                kr = rotary(kr, self.rope_dim, self.rope_theta, 0, self.rope_interleave)
        with device_scope("proj"):
            kr = jnp.broadcast_to(kr, (b, h, t, self.rope_dim))
            k = jnp.concatenate([kv[..., :nope], kr], axis=-1)
            # the kernels' three operands, kept by a block's 'full' remat: its
            # recompute stops at `ckr` and the norm (the docstring's last paragraph).
            # Under `proj`: XLA books the fusions that write them to the tag's
            # scope, and `attend` stays the kernels' own time
            q, k, v = _keep((q, k, kv[..., nope:]))
        with device_scope("attend"):
            o = att.attend(q, k, v, causal=True, mask=mask)
        with device_scope("out"):
            y = ops.dot(o.transpose(0, 2, 1, 3).reshape(b, t, h * self.v_dim), params["Wo"])
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, state


# ---------------------------------------------------------------------------
# double-gated short convolution
# ---------------------------------------------------------------------------
def short_conv(u, w):
    """The causal depthwise convolution of u [b, t, f] (float32) with the
    taps w [cw, f], tap cw - 1 - s on the token s places back, zeros before
    the sequence; no bias, no activation. Plain [b, t, f]: a mixer with no
    recurrence needs no chunks. In float32, so that the cw shifted reads —
    and in the backward their cotangents — add up there and round once."""
    t, cw = u.shape[1], w.shape[0]
    return sum(jnp.pad(u, ((0, 0), (s, 0), (0, 0)))[:, :t] * w[cw - 1 - s] for s in range(cw))


@register_layer
@dataclass
class GatedShortConv(Layer):
    """A mixer of two gates around a short convolution (the LFM2 `conv`
    operator): [B | C | z] = x Win, Win [f, 3 f]; u = B z; c = `short_conv`
    (u; conv [conv_width, f]); y = (C c) Wout, Wout [f, f]. No bias, no
    activation, no recurrence: two products around three elementwise passes
    over [b, t, f], which run in float32 between the projections' dtype."""

    conv_width: int = 3

    def output_type(self, input_type):
        return input_type

    def init_params(self, rng, input_type):
        f = input_type.size
        r = jax.random.split(rng, 3)
        return {"Win": _w(self, r[0], (f, 3 * f)),
                "conv": jax.random.uniform(r[1], (self.conv_width, f), F32,
                                           -1.0, 1.0) * self.conv_width ** -0.5,
                "Wout": _w(self, r[2], (f, f))}

    def regularizable(self, params):
        return {k: v for k, v in params.items() if k.startswith("W")}

    def apply(self, params, x, *, state, train, rng, mask=None):
        with device_scope("proj"):
            bcz = ops.dot(x, params["Win"])
        with device_scope("gates"):
            b_, c_, z = (a.astype(F32) for a in jnp.split(bcz, 3, axis=-1))
            u = b_ * z
            if mask is not None:  # a padded token enters no convolution window
                u = u * mask[..., None].astype(F32)
        with device_scope("conv"):
            c = short_conv(u, params["conv"])
        with device_scope("gates"):
            y = (c_ * c).astype(bcz.dtype)
        with device_scope("out"):
            y = ops.dot(y, params["Wout"])
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


@register_layer
@dataclass
class GatedMLP(Layer):
    """A dense feed-forward of `width` with an expert's non-linearity
    (`EXPERT_ACTS`): "swiglu" (silu(x Wg) (x Wu)) Wd with Wgu = [gate | up]
    one matrix; "relu2" relu(x Wu)^2 Wd. No bias. The leading dense layer
    of an otherwise routed stack."""

    width: int = 1024
    act: str = "swiglu"

    def output_type(self, input_type):
        return input_type

    def init_params(self, rng, input_type):
        f = input_type.size
        _, wide, up = EXPERT_ACTS[self.act]
        r = jax.random.split(rng, 2)
        return {up: _w(self, r[0], (f, wide * self.width)),
                "Wd": _w(self, r[1], (self.width, f))}

    def apply(self, params, x, *, state, train, rng, mask=None):
        act, _, up = EXPERT_ACTS[self.act]
        y = ops.dot(act(ops.dot(x, params[up])), params["Wd"])
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, state


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


# The exchange's buffers. A rank's assignments, sorted by expert, fall into one
# run a destination rank; the pair buffer [ranks, pair_rows, f] holds the first
# `pair_rows` of each run. `src` [ranks * pair_rows] is the assignment whose
# token a buffer row carries (any token where the run is shorter: such a row
# weighs 0 on its way back), `row` [k * n] the buffer row of an assignment
# (clipped where the run was cut: `kept` says which). Both ways across are
# gathers, forward and backward, as across the one-rank buffer above. The way
# OUT (`_rows_out`, then the `all_to_all`, then the receiver's `_permuted`)
# keeps no row: its backward reads index arrays alone. The way HOME
# (`_rows_home`) is ONE rule from the experts' `ys` to the tokens' sum whose
# residuals are `ys`, the weights and the index arrays — nothing that crossed:
# the rows that came back are `ys` in another order on another rank, bit for
# bit, so the one thing the backward wants of them, a slot's weight gradient
# <g[token], row>, is taken on the expert side from `ys` and the cotangent rows
# that arrive there anyway, and goes home as a scalar a row. A block's
# recompute then has no use for the permutation back or the `all_to_all` back.
@jax.custom_vjp
def _rows_out(xf, src, row, kept):
    """xf [n, f] -> the pair buffers' rows [ranks * pair_rows, f]."""
    return xf[src % xf.shape[0]]


def _rows_out_fwd(xf, src, row, kept):
    return _rows_out(xf, src, row, kept), (row, kept, xf.shape[0])


def _rows_out_bwd(res, g):
    row, kept, n = res
    mine = g[row].astype(F32) * kept[:, None]   # an assignment cut off: no gradient
    return mine.reshape(-1, n, g.shape[-1]).sum(axis=0).astype(g.dtype), None, None, None


_rows_out.defvjp(_rows_out_fwd, _rows_out_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _rows_home(ys, wt, src, row, valid, order2, inv2, axis, ranks):
    """The experts' rows ys [ranks * pair_rows, f], in the expert side's sorted
    order, home to their tokens: into pair-buffer order (`inv2`, the inverse
    of `order2`), `all_to_all` over `axis` to the ranks they came from, and
    there out[n] = sum over the token's k slots of wt[slot, n] ret[row of
    (slot, n)], float32. `wt` [k, n] is zero for an assignment that was cut.
    ONE rule from the expert side to the token side, so that nothing that
    crossed is a residual (`_rows_home_bwd`)."""
    k, n = wt.shape
    with device_scope("gather"):
        back = ys[inv2]
    with device_scope("exchange"), device_scope("back"):
        ret = lax.all_to_all(back.reshape(ranks, -1, back.shape[-1]), axis, 0, 0)
    with device_scope("combine"):
        ret = ret.reshape(back.shape)
        return jnp.sum(ret[row].reshape(k, n, -1).astype(F32) * wt[..., None], axis=0)


def _rows_home_fwd(ys, wt, src, row, valid, order2, inv2, axis, ranks):
    return (_rows_home(ys, wt, src, row, valid, order2, inv2, axis, ranks),
            (ys, wt, src, row, valid, order2, inv2))


def _rows_home_bwd(axis, ranks, res, g):
    ys, wt, src, row, valid, order2, inv2 = res
    n = wt.shape[1]
    # as `_from_buffer_bwd`: the cotangent crosses in the buffer's dtype. It
    # crosses UNweighted, each row's weight beside it, and is weighted where the
    # experts are: there a slot's weight gradient <g[token], ys[position]> is
    # taken from `ys` itself — what came back of it on the forward is `ys` in
    # another order on another rank, bit for bit — and goes home a scalar a row
    with device_scope("combine"):
        # the barrier keeps the pad of the grouped product's columns (the caller
        # cuts them from `out`) in front of the gather, as `exchanged` does on the
        # way out: behind it the pad is a pass over the buffer
        rows = lax.optimization_barrier(g.astype(ys.dtype))[src % n]
        w = jnp.where(valid, wt.reshape(-1)[src], 0.0)
    with device_scope("exchange"), device_scope("back"):
        rows = lax.all_to_all(rows.reshape(ranks, -1, rows.shape[-1]), axis, 0, 0)
        w = lax.all_to_all(w.reshape(ranks, -1), axis, 0, 0)
    with device_scope("gather"):
        rows = rows.reshape(ys.shape)[order2].astype(F32)
        w = w.reshape(-1)[order2]
    with device_scope("combine"):
        d_ys = (rows * w[:, None]).astype(ys.dtype)
        dots = jnp.sum(rows * ys.astype(F32), axis=-1)
    with device_scope("gather"):
        dots = dots[inv2]
    with device_scope("exchange"), device_scope("back"):
        dots = lax.all_to_all(dots.reshape(ranks, -1), axis, 0, 0)
    with device_scope("combine"):
        d_wt = dots.reshape(-1)[row].reshape(wt.shape)
    return d_ys, d_wt, None, None, None, None, None


_rows_home.defvjp(_rows_home_fwd, _rows_home_bwd)


@jax.custom_vjp
def _permuted(x, order, inv):
    """x[order] for a permutation `order` with inverse `inv`: a gather both
    ways (autodiff would scatter-add the cotangent)."""
    return x[order]


def _permuted_fwd(x, order, inv):
    return x[order], (inv,)


def _permuted_bwd(res, g):
    return g[res[0]], None, None


_permuted.defvjp(_permuted_fwd, _permuted_bwd)


#: The most bytes of `h` — the first grouped product's output
#: [capacity, wide x grouped_width(expert_width)], what the activation reads —
#: that `RoutedExperts` tags `REMAT_KEEP`, compared with the traced array's own
#: bytes. `h` is the narrow side of the block and two thirds of a swiglu
#: expert's forward work to remake: kept, a block's 'full' recompute drops
#: `xs Wgu` (PERF.md section 6, PR 50). At 2 x 8192 tokens in bfloat16 a
#: layer's `h` is 288 MiB (Kanana), 320 (Qwen3-Next, and Laguna at 1 x 8192),
#: 384 (LFM2, Nemotron) and 512 (Kimi-Linear) in the benchmark's six expert
#: configurations, four expert layers each: the first three keep it. At 384
#: Nemotron's step would hold 16.09 GB by `memory_analysis()`, over the 16e9
#: its compile test allows. The cost is a LAYER's and the chip's memory is
#: shared by all of them — `bound x expert layers` more under 'full'; a budget
#: for the stack belongs to whoever owns the remat policy (ROADMAP S10 (e)).
H_KEEP_BYTES = 320 * 2 ** 20

#: bins a unit of `pair_fill_hist`: an exchanging layer books each step's
#: fullest pair (assignments one rank had for another over the pair buffer's
#: rows, demanded, so it can pass 1) into one of 2 x FILL_BINS bins over
#: [0, 2) — a histogram adds up over steps where a maximum does not, so a
#: fit's fullest pair is its highest bin's upper edge, 1 / FILL_BINS fine
FILL_BINS = 128


@register_layer
@dataclass
class RoutedExperts(Layer):
    """Experts behind a router, for a rank that holds `experts_held` =
    (first, count) of `n_experts` (default: all), plus a shared expert
    (`shared_width` 0: none, no `shared_*` leaf, nothing added). The
    router scores all `n_experts`, keeps the `top_k` largest and
    renormalises them over the chosen wherever they live; this layer adds
    the terms of its own experts and leaves the others' out: a rank alone
    runs without the exchange that would bring other ranks' tokens. With an
    `exchange_axis` the layer holds ALL experts, split over that mesh axis's
    ranks, and exchanges: where the ambient mesh has the axis larger than 1
    every rank routes its own tokens, sends each to the ranks that hold its
    experts and takes the results back (`exchanged`: a `shard_map` island
    with two `lax.all_to_all`s; its pair buffers hold `capacity_factor` x the
    load one rank expects for another, `pair_rows`); anywhere else the layer
    lowers to the program it always was. `partition_specs` tells the wrapper
    which leaves live split (docs/HYBRID_LAYERS.md, the exchange).

    Two recipes share everything below the scores. `scoring` "softmax":
    the weights are the top-k of the softmax, renormalised over the chosen
    (`norm_topk`). "sigmoid": every expert is scored sigmoid(x Wr) on its
    own; the k are CHOSEN by score + `select_bias` (a leaf no gradient
    reaches: it chooses, it does not weigh), weighted by the bare scores,
    renormalised (over their sum + `norm_eps`). Under BOTH scorings the
    weights are then scaled by `routed_scale`. `expert_act` (`EXPERT_ACTS`) is
    the non-linearity of routed and shared experts alike; `shared_gated`
    multiplies the shared expert by sigmoid(x w) or adds it as it is.

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

    What a block's 'full' remat keeps of this layer (`REMAT_KEEP`; outside a
    `jax.checkpoint`, and under every other policy, the tags lower to
    nothing): `h`, the first product's output, where its bytes as traced are
    at most `H_KEEP_BYTES`; the sort's `order`, `inv` and group sizes; the
    router's logits and, under the sigmoid recipe, the chosen ids. The block's
    recompute then neither sorts nor runs the router's product again (nor,
    sigmoid, selects) and drops `xs Wgu`; it still gathers the buffer, applies
    the activation and runs `act(h) Wd` (the router weights' gradient reads
    its rows). Where the layer EXCHANGES it also keeps `xs`, the rows that
    arrived in the receiver's order (what `dWgu = xs^T dh` reads; two passes
    over the buffer and a way over the interconnect to remake), with the
    receiver's sort; and nothing that came BACK is a residual (`_rows_home`):
    the recompute sends no row across the interconnect, neither out nor back —
    it runs the products and the activation on the expert side and the index
    arithmetic of `bucket` on the token side. The backward sends the
    cotangents of both ways and, beside the rows coming back's, a weight and
    a dot product a row. The kept arrays have static shapes: nothing follows
    the routing.

    State `counters` (int32, wrapping; per-fit differences are exact):
    `steps`, `load` [count] assignments routed to each held expert,
    `dropped`, `capacity` (buffer rows offered), `ratio_sum` (float32 sum
    over steps of max-over-mean load); with an `exchange_axis` also
    `rank_ratio_sum` (the same over the ranks' loads) and `pair_fill_hist`
    (`FILL_BINS`), all summed over the ranks. `telemetry.fit_log()` reports them
    per fit under `experts` (`counter_summary`), with `h_kept_mb`: the MB of
    `h` a step that carry the tag as the training step was last traced, 0.0
    beyond the bound — "tagged", since the layer cannot see whether a 'full'
    checkpoint wraps it, and only there does the tag hold bytes — and, for a
    layer with an `exchange_axis`, `exchange_bytes` (what one rank sends the
    others in a step's forward and backward) and `exchange_kept_mb` (the MB of
    arrived rows a rank that carry the tag; both 0 on one rank)."""

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
    norm_eps: float = 1e-20
    exchange_axis: Optional[str] = None

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

    def pair_rows(self, rows: int, ranks: int) -> int:
        """Rows of the buffer one rank sends another for its `rows` tokens: the
        factor times the expected count (rows x top_k / ranks), to a multiple
        of 128, at most every assignment of the sender."""
        c = -(-int(self.capacity_factor * rows * self.top_k / ranks) // 128) * 128
        return max(1, min(c, rows * self.top_k))

    def exchange_ranks(self) -> int:
        """How many ranks share this layer's experts where it is traced: the
        size of the ambient mesh's `exchange_axis` (`ParallelWrapper` calls
        its step under `jax.set_mesh`), 1 without the axis, without a mesh or
        inside a `shard_map` that is already manual over it."""
        if self.exchange_axis is None:
            return 1
        am = jax.sharding.get_abstract_mesh()
        if am.empty or self.exchange_axis not in am.axis_names \
                or self.exchange_axis in am.manual_axes:
            return 1
        return int(am.shape[self.exchange_axis])

    def partition_specs(self, params, axis_sizes, model_axis: str = "model"):
        """The expert matrices split on their expert dimension over
        `exchange_axis` where the mesh has it larger than 1 (a rank holds
        n_experts / ranks whole experts, their Adam moments with them);
        everything else — router, shared expert, selection bias — whole on
        every rank."""
        from jax.sharding import PartitionSpec as P

        ranks = axis_sizes.get(self.exchange_axis, 1) if self.exchange_axis else 1
        split = {self._act()[2], "Wd"} if ranks > 1 else set()
        return {k: P(self.exchange_axis) if k in split else P() for k in params}

    def output_type(self, input_type):
        return input_type

    def init_params(self, rng, input_type):
        f = input_type.size
        first, count = self.held()
        if self.exchange_axis is not None and count != self.n_experts:
            raise ValueError(
                f"exchange_axis={self.exchange_axis!r} spreads ALL {self.n_experts} experts "
                f"over the axis; experts_held={self.experts_held} holds a share of them")
        if first < 0 or first + count > self.n_experts:
            raise ValueError(f"experts_held={self.experts_held} outside 0..{self.n_experts}")
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring={self.scoring!r}: 'softmax' or 'sigmoid'")
        e, s = self.expert_width, self.shared_width
        _, wide, up = self._act()
        r = jax.random.split(rng, 6)
        p = {"router": _w(self, r[0], (f, self.n_experts)),
             up: _w(self, r[1], (count, f, wide * e)),
             "Wd": _w(self, r[2], (count, e, f))}
        if s:
            p.update({"shared_" + up: _w(self, r[3], (f, wide * s)),
                      "shared_Wd": _w(self, r[4], (s, f))})
            if self.shared_gated:
                p["shared_gate"] = _w(self, r[5], (f, 1))
        if self.scoring == "sigmoid":
            p["select_bias"] = jnp.zeros((self.n_experts,), F32)
        return p

    def init_state(self, input_type):
        _, count = self.held()
        zero = lambda: jnp.zeros((), jnp.int32)  # noqa: E731 — a buffer each: state is donated
        counters = {"steps": zero(), "load": jnp.zeros((count,), jnp.int32),
                    "dropped": zero(), "capacity": zero(),
                    "ratio_sum": jnp.zeros((), F32)}
        if self.exchange_axis is not None:
            counters.update(rank_ratio_sum=jnp.zeros((), F32),
                            pair_fill_hist=jnp.zeros((2 * FILL_BINS,), jnp.int32))
        return {"counters": counters}

    def regularizable(self, params):
        return {k: v for k, v in params.items() if "W" in k}

    def counter_summary(self, added):
        """Per-step means of the counters over a fit, under `experts`."""
        steps = int(added["steps"][0])
        routed, dropped = int(added["load"].sum()), int(added["dropped"][0])
        entry = {
            "steps": steps,
            "assignments_per_step": routed / max(steps, 1),
            "load_max_over_mean": float(added["ratio_sum"][0]) / max(steps, 1),
            "dropped_assignments": dropped,
            "capacity_fill": (routed - dropped) / max(int(added["capacity"][0]), 1),
            "h_kept_mb": getattr(self, "_h_kept_mb", 0.0),
        }
        if "pair_fill_hist" in added:
            # an exchanging layer: `load`, `dropped` and `capacity` are sums over
            # the ranks; the fullest pair of the fit from the steps' histogram
            filled = np.flatnonzero(added["pair_fill_hist"])
            entry.update(
                capacity=int(added["capacity"][0]) // max(steps, 1),
                pair_fill_max=(int(filled[-1]) + 1) / FILL_BINS if filled.size else 0.0,
                rank_load_max_over_mean=float(added["rank_ratio_sum"][0]) / max(steps, 1),
                exchange_bytes=getattr(self, "_exchange_bytes", 0),
                exchange_kept_mb=getattr(self, "_exchange_kept_mb", 0.0))
        return "experts", entry

    def route(self, params, xf):
        """(weights [n, top_k] float32, expert ids [n, top_k]). The logits are
        tagged `REMAT_KEEP`, so a block's 'full' recompute does not run the
        router's product again, and the sigmoid recipe's ids too, so that it
        does not select again either. (The softmax recipe's selection stays in
        the recompute: `top_k`'s own derivative reads the ids it made, not a
        tagged copy, and gathering the weights at tagged ids instead costs
        more than the selection — 0.84 against 0.24 ms a layer on a v5e at
        [8192, 256], PERF.md section 6, PR 50.)"""
        logits = _keep(jnp.matmul(xf.astype(F32), params["router"],
                                  precision=lax.Precision.HIGHEST))
        if self.scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            idx = _keep(lax.top_k(scores + lax.stop_gradient(params["select_bias"]),
                                  self.top_k)[1])
            top = jnp.take_along_axis(scores, idx, axis=-1)
            if self.norm_topk:
                top = top / (jnp.sum(top, axis=-1, keepdims=True) + self.norm_eps)
            return top * self.routed_scale, idx
        top, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), self.top_k)
        if self.norm_topk:
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        return top * self.routed_scale, idx

    def routed(self, params, xf, top, idx):
        """The held experts' terms for tokens xf [n, f] -> ([n, f] float32,
        routed count per held expert, dropped assignments)."""
        n, f = xf.shape
        k = self.top_k
        first, count = self.held()
        cap = self.capacity(n)
        with device_scope("sort"):
            local = idx.T - first               # [k, n]: assignment = slot * n + token
            key = jnp.where((local >= 0) & (local < count), local, count).reshape(-1)
            order = jnp.argsort(key, stable=True)   # by expert; what is held comes first
            inv = jnp.argsort(order)
            starts = jnp.searchsorted(key[order], jnp.arange(count + 1), side="left")
            bounds = jnp.minimum(starts, cap)
            sizes = bounds[1:] - bounds[:-1]
            sizes = sizes.at[-1].add(cap - bounds[-1])   # the padding is computed
            # two argsorts to remake, under 2 MB to hold
            order, inv, sizes = _keep((order, inv, sizes))
        # the buffer is born at the width the grouped product runs well at:
        # zero columns added to the TOKENS here and cut from the tokens below.
        # The barrier keeps XLA from moving the pad behind the gather, where it
        # is a pass over the buffer (1.83 ms, 8 a step at Nemotron's size)
        padded = ops.grouped_width(f)
        with device_scope("gather"):
            if padded != f:
                xf = lax.optimization_barrier(jnp.pad(xf, ((0, 0), (0, padded - f))))
            xs = _to_buffer(xf, order, inv, cap)
        act, wide, up = self._act()
        with device_scope("product"):
            h = ops.grouped_dot(xs, params[up], sizes, wide)
            # most of the block's forward work and its narrow side: kept within the
            # bound. The MB tagged are a fact of this trace: `apply` reports the
            # training step's
            h_bytes = h.size * h.dtype.itemsize
            tagged = h_bytes <= H_KEEP_BYTES
            if tagged:
                h = _keep(h)
            self._h_tagged_mb = tagged * h_bytes / 1e6
            ys = ops.grouped_dot(act(h), params["Wd"], sizes)
        with device_scope("combine"):
            # a slot counts when its expert is held and its position is inside the
            # buffer; the rows of the others (the last group's padding) weigh 0
            kept = (key < count) & (inv < cap)
            out = _from_buffer(ys, jnp.where(kept, top.T.reshape(-1), 0.0).reshape(k, n),
                               order, inv)[:, :f]
        with device_scope("counters"):
            load = (starts[1:] - starts[:-1]).astype(jnp.int32)
            dropped = jnp.maximum(starts[-1] - cap, 0).astype(jnp.int32)
        return out, load, dropped

    def exchanged(self, params, xf, ranks: int):
        """The routed terms of ALL experts for tokens xf [n, f] whose rows, like
        the experts, are split over the `ranks` of `exchange_axis`: a
        `shard_map` island in which every rank routes its own tokens, buckets
        their rows by destination rank into a [ranks, pair_rows, f] buffer
        (`pair_rows`; a run beyond it is cut and counted), sends each rank its
        part (`all_to_all`), sorts what it received by its own experts, runs
        the two grouped products, sends the rows back the way they came and
        adds them up weighted, in token order. The transpose of the island is
        the same exchange run backwards: an expert's gradient is complete on
        the rank that holds it, the router's is summed over the ranks.
        -> ([n, f] float32, assignments an expert [n_experts] and dropped
        assignments summed over the ranks, the fullest pair's demand in rows,
        a pair buffer's rows)."""
        from jax.sharding import PartitionSpec as P

        axis, k = self.exchange_axis, self.top_k
        if self.n_experts % ranks or xf.shape[0] % ranks:
            raise ValueError(f"{self.n_experts} experts and {xf.shape[0]} tokens over "
                             f"{ranks} ranks of {axis!r}: both must divide")
        held = self.n_experts // ranks
        n, f = xf.shape[0] // ranks, xf.shape[1]
        pair = self.pair_rows(n, ranks)
        rows = ranks * pair
        act, wide, up = self._act()
        padded = ops.grouped_width(f)

        def island(p, xf):
            with device_scope("route"):
                top, idx = self.route(p, xf)
            with device_scope("sort"):
                key = idx.T.reshape(-1)             # assignment = slot * n + token -> its expert
                order = jnp.argsort(key, stable=True)
                inv = jnp.argsort(order)
                starts = jnp.searchsorted(key[order], jnp.arange(self.n_experts + 1),
                                          side="left")
                order, inv, starts = _keep((order, inv, starts))
            with device_scope("bucket"):
                # the sorted assignments are one run a destination rank
                first = starts[:-1:held]                                  # [ranks]
                demand = starts[held::held] - first
                at = jnp.arange(pair)
                valid = at[None, :] < demand[:, None]                     # [ranks, pair]
                src = order[jnp.minimum(first[:, None] + at[None, :], k * n - 1)]
                local = jnp.where(valid, key[src] % held, held)           # held: no row here
                to = key // held
                off = inv - first[to]
                kept = off < pair
                row = to * pair + jnp.minimum(off, pair - 1)
                src, valid = src.reshape(-1), valid.reshape(-1)
                xc = ops._mixed_cast(xf, xf)[0]
                if padded != f:     # born at the grouped product's width, as `routed`'s
                    xc = lax.optimization_barrier(jnp.pad(xc, ((0, 0), (0, padded - f))))
                send = _rows_out(xc, src, row, kept)
            with device_scope("exchange"), device_scope("out"):
                got = lax.all_to_all(send.reshape(ranks, pair, padded), axis, 0, 0)
                got_local = lax.all_to_all(local, axis, 0, 0)
            with device_scope("sort"):
                key2 = got_local.reshape(-1)        # by my expert; the rows no one sent last
                order2 = jnp.argsort(key2, stable=True)
                inv2 = jnp.argsort(order2)
                starts2 = jnp.searchsorted(key2[order2], jnp.arange(held + 1), side="left")
                sizes = starts2[1:] - starts2[:-1]
                sizes = sizes.at[-1].add(rows - starts2[-1])    # the padding is computed
                order2, inv2, sizes = _keep((order2, inv2, sizes))
            with device_scope("gather"):
                # what arrived is kept: two passes over the buffer and its way
                # over the interconnect to remake (`REMAT_KEEP`'s rule)
                xs = _keep(_permuted(got.reshape(rows, padded), order2, inv2))
            with device_scope("product"):
                h = ops.grouped_dot(xs, p[up], sizes, wide)
                h_bytes = h.size * h.dtype.itemsize
                tagged = h_bytes <= H_KEEP_BYTES
                if tagged:
                    h = _keep(h)
                self._h_tagged_mb = tagged * h_bytes / 1e6
                ys = ops.grouped_dot(act(h), p["Wd"], sizes)
            with device_scope("combine"):
                wt = jnp.where(kept, top.T.reshape(-1), 0.0).reshape(k, n)
            # gather, exchange/back and combine, one rule forward and backward
            out = _rows_home(ys, wt, src, row, valid, order2, inv2, axis, ranks)[:, :f]
            with device_scope("counters"):
                load = lax.psum((starts[1:] - starts[:-1]).astype(jnp.int32), axis)
                dropped = lax.psum(jnp.sum(jnp.maximum(demand - pair, 0)).astype(jnp.int32),
                                   axis)
                fullest = lax.pmax(jnp.max(demand).astype(jnp.int32), axis)
            # what one chip sends in a step's forward and backward: the rows both
            # ways and their cotangents, each row's expert id out and, in the
            # backward, its weight out and its weight gradient home (a block's
            # recompute sends nothing); and the MB of arrived rows tagged
            self._exchange_traced = (ranks - 1) * pair * (
                4 * padded * send.dtype.itemsize + local.dtype.itemsize
                + 2 * wt.dtype.itemsize)
            self._exchange_kept_traced = xs.size * xs.dtype.itemsize / 1e6
            return out, load, dropped, fullest

        # manual over every axis still automatic here, as `kernel_call.per_batch_shard`
        am = jax.sharding.get_abstract_mesh()
        out, load, dropped, fullest = jax.shard_map(
            island, in_specs=(self.partition_specs(params, {axis: ranks}), P(axis)),
            out_specs=(P(axis), P(), P(), P()),
            axis_names=set(am.axis_names) - set(am.manual_axes), check_vma=False)(params, xf)
        return out, load, dropped, fullest, pair

    def apply(self, params, x, *, state, train, rng, mask=None):
        shape = x.shape
        xf = x.reshape(-1, shape[-1])
        ranks = self.exchange_ranks()
        if ranks > 1:
            out, load, dropped, fullest, pair = self.exchanged(params, xf, ranks)
            offered = ranks * ranks * pair
        else:
            with device_scope("route"):
                top, idx = self.route(params, xf)
            out, load, dropped = self.routed(params, xf, top, idx)
            offered = self.capacity(xf.shape[0])
            fullest, pair = xf.shape[0] * self.top_k, offered      # one pair: every assignment
        if self.shared_width:
            act, _, up = self._act()
            with device_scope("shared"):
                gate = (jax.nn.sigmoid(ops.dot(xf, params["shared_gate"]).astype(F32))
                        if self.shared_gated else 1.0)
                shared = ops.dot(act(ops.dot(xf, params["shared_" + up])), params["shared_Wd"])
            with device_scope("combine"):
                out = out + gate * shared.astype(F32)
        with device_scope("combine"):
            y = out.astype(x.dtype).reshape(shape)
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        if train:
            self._h_kept_mb = self._h_tagged_mb   # a fact of the traced shapes: no device work
            with device_scope("counters"):
                c = state["counters"]
                mean = jnp.maximum(jnp.mean(load.astype(F32)), 1e-9)
                counters = {
                    "steps": c["steps"] + 1, "load": c["load"] + load,
                    "dropped": c["dropped"] + dropped,
                    "capacity": c["capacity"] + offered,
                    "ratio_sum": c["ratio_sum"] + jnp.max(load.astype(F32)) / mean}
                if self.exchange_axis is not None:
                    self._exchange_bytes = getattr(self, "_exchange_traced", 0) if ranks > 1 else 0
                    self._exchange_kept_mb = self._exchange_kept_traced if ranks > 1 else 0.0
                    by_rank = load.reshape(ranks, -1).sum(axis=1).astype(F32)
                    fill_bin = jnp.minimum(fullest * FILL_BINS // pair, 2 * FILL_BINS - 1)
                    counters.update(
                        rank_ratio_sum=c["rank_ratio_sum"] + jnp.max(by_rank) / (
                            jnp.maximum(jnp.mean(by_rank), 1e-9)),
                        pair_fill_hist=c["pair_fill_hist"].at[fill_bin].add(1))
                state = {"counters": counters}
        return y, state
