"""Scaled-dot-product attention primitives.

The reference has NO attention anywhere (pre-transformer, 2017 — SURVEY.md §5
'Long-context / sequence parallelism: absent'); its long-sequence story is
truncated BPTT. Attention + ring attention are the net-new TPU-first
capabilities the north star requires, so the primitives live here in `ops`
next to the matmul/conv wrappers.

`attend` is the one entry the layers call: it picks among the
formulations below, ring attention (ops/ring.py) and the Pallas flash
kernels (ops/pallas_kernels.py) by `choose_impl`'s rule.

Three formulations, all numerically the softmax(QKᵀ/√d)·V contraction:

  sdpa           — one fused einsum chain; XLA fuses scale/mask/softmax into
                   the MXU matmuls. Right choice whenever [t, t] scores fit
                   in HBM.
  blockwise      — lax.scan over key/value chunks with an online (running
                   max/sum) softmax — the flash-attention recurrence. O(t)
                   memory instead of O(t²); also the inner loop reused by
                   ring attention (ops/ring.py), where the "next chunk"
                   arrives over ICI instead of from HBM.
  online_block   — one online-softmax accumulation step, shared by blockwise
                   and ring attention.

Shapes: q [b, h, tq, d], k [b, h, tk, d], v [b, h, tk, dv] (dv = d unless a
head's keys carry a part its values lack). Masks are key-padding masks
[b, tk] (1 = attend) — the BTF mask convention the RNN layers use; `causal`
adds the lower-triangular constraint, and a `window` (with `causal`) the
band's other edge: query i sees the `window` keys i - window + 1 .. i, its
own among them (a window of 512 is 512 keys, 511 of them back). Every
formulation takes it; the flash kernels visit the blocks the band touches
and no other; ring attention refuses it by name.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.ops import kernel_call
from deeplearning4j_tpu.ops import linear as ops
from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.ops import rope_kernels

NEG_INF = -1e30  # finite ⇒ fully-masked rows give exp(·)=0, never NaN


def _scores(q, k, scale):
    # [b, h, tq, d] x [b, h, tk, d] -> [b, h, tq, tk]
    s = ops.dot_general(
        q * scale, k, (((3,), (3,)), ((0, 1), (0, 1)))
    )
    # softmax and the online-softmax recurrence (max/exp/sum, the corr
    # factor across ring blocks) must run in f32 even under the bf16
    # mixed-precision policy — bf16's 8-bit mantissa compounds per block
    return s.astype(jnp.float32) if s.dtype == jnp.bfloat16 else s


def _apply_masks(s, *, mask, causal, q_offset, k_offset, tq, tk, dtype,
                 window=None):
    if window is not None and not causal:
        raise ValueError(f"window={window} needs causal=True: it is the number of "
                         f"keys a query looks back over, its own among them")
    if mask is not None:
        s = jnp.where(mask[:, None, None, :].astype(bool), s, NEG_INF)
    if causal:
        qi = q_offset + jnp.arange(tq)
        ki = k_offset + jnp.arange(tk)
        keep = qi[:, None] >= ki[None, :]
        if window is not None:
            keep = keep & (qi[:, None] - ki[None, :] < window)
        s = jnp.where(keep[None, None], s, NEG_INF)
    return s


def sdpa(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    mask: Optional[jnp.ndarray] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Full-materialization attention: softmax(QKᵀ·scale [+mask]) V."""
    d = q.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    s = _scores(q, k, jnp.asarray(scale, q.dtype))
    s = _apply_masks(s, mask=mask, causal=causal, q_offset=0, k_offset=0,
                     tq=q.shape[2], tk=k.shape[2], dtype=q.dtype, window=window)
    p = jax.nn.softmax(s, axis=-1)
    # primitives return q.dtype regardless of policy/path (blockwise
    # delegates here for short sequences — one output dtype per primitive)
    return ops.dot_general(p, v, (((3,), (2,)), ((0, 1), (0, 1)))).astype(q.dtype)


def online_block(
    acc: Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray],
    q: jnp.ndarray,
    k_blk: jnp.ndarray,
    v_blk: jnp.ndarray,
    *,
    scale,
    mask_blk: Optional[jnp.ndarray] = None,
    causal: bool = False,
    q_offset=0,
    k_offset=0,
    window: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One step of the online-softmax recurrence.

    acc = (o [b,h,tq,d] unnormalized, l [b,h,tq] row sum, m [b,h,tq] row max).
    Offsets are the global positions of q/k block starts (traced or static),
    needed for causal masking of remote blocks in ring attention. A row that
    sees no key of this block (all behind its window) adds exp(0) a key to a
    state whose max is still NEG_INF; the block that holds its own key comes
    later and its correction exp(NEG_INF - m) = 0 wipes that out.
    """
    o, l, m = acc
    s = _scores(q, k_blk, jnp.asarray(scale, q.dtype))
    s = _apply_masks(s, mask=mask_blk, causal=causal, q_offset=q_offset,
                     k_offset=k_offset, tq=q.shape[2], tk=k_blk.shape[2],
                     dtype=q.dtype, window=window)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    pv = ops.dot_general(p, v_blk, (((3,), (2,)), ((0, 1), (0, 1))))
    # accumulators stay in the carry dtype (f32 — see online_init) so the
    # scan carry is dtype-stable under the mixed policy
    o_new = o * corr[..., None] + pv.astype(o.dtype)
    return o_new, l_new, m_new


def online_init(q, dv: Optional[int] = None):
    """The empty online-softmax state for queries q and values `dv` wide
    (default: as wide as q)."""
    b, h, tq, d = q.shape
    acc_dtype = jnp.float32 if q.dtype == jnp.bfloat16 else q.dtype
    return (
        jnp.zeros((b, h, tq, dv or d), acc_dtype),
        jnp.zeros((b, h, tq), acc_dtype),
        jnp.full((b, h, tq), NEG_INF, acc_dtype),
    )


def online_finish(acc):
    o, l, m = acc
    return o / jnp.maximum(l, 1e-37)[..., None]


def online_chunks(acc, q, k, v, *, scale, mask=None, causal=False,
                  q_offset=0, k_offset=0, block_size: int = 512,
                  window: Optional[int] = None):
    """Scan K/V chunks of `block_size` into an online-softmax state —
    the shared flash inner loop behind `blockwise` and ring attention's
    per-hop chunking (ops/ring.py). Ragged tails are PADDED (padded
    keys masked dead), never silently widened back to one full block:
    peak memory stays O(tq · block_size) regardless of tk. Offsets are
    the global positions of the q block and of k[0] (traced or static)."""
    b, h, tk, d = k.shape
    nblk = -(-tk // block_size)
    pad = nblk * block_size - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        base = jnp.ones((b, tk), q.dtype) if mask is None else mask
        mask = jnp.pad(base, ((0, 0), (0, pad)))
    kb = k.reshape(b, h, nblk, block_size, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, nblk, block_size, -1).transpose(2, 0, 1, 3, 4)
    mb = (mask.reshape(b, nblk, block_size).transpose(1, 0, 2)
          if mask is not None else None)

    def step(acc, inp):
        if mb is not None:
            i, kc, vc, mc = inp
        else:
            i, kc, vc = inp
            mc = None
        return online_block(acc, q, kc, vc, scale=scale, mask_blk=mc,
                            causal=causal, q_offset=q_offset,
                            k_offset=k_offset + i * block_size, window=window), None

    xs = (jnp.arange(nblk), kb, vb) + ((mb,) if mb is not None else ())
    acc, _ = lax.scan(step, acc, xs)
    return acc


def blockwise(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    mask: Optional[jnp.ndarray] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    block_size: int = 512,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Flash-style O(t) memory attention: lax.scan over key/value chunks
    (every chunk, whatever the window: the band is a mask here)."""
    d = k.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    if k.shape[2] <= block_size:
        return sdpa(q, k, v, mask=mask, causal=causal, scale=scale, window=window)
    acc = online_chunks(online_init(q, v.shape[-1]), q, k, v, scale=scale, mask=mask,
                        causal=causal, block_size=block_size, window=window)
    return online_finish(acc).astype(q.dtype)


# ---------------------------------------------------------------------------
# the door: which implementation runs a layer's attention
# ---------------------------------------------------------------------------


#: float32 score bytes [b, h, t, t] that `sdpa` may be handed when the caller
#: asked for a kernel and the shape rule declines it: beyond this the call
#: raises instead (2 x 32 heads at t 8192 are 17 GB)
SDPA_SCORE_BYTES = 2 ** 32


def _head_widths(d) -> Tuple[int, int]:
    """(key width, value width) from a pair, or from one width for both."""
    return (d, d) if isinstance(d, int) else (int(d[0]), int(d[1]))


def _kernel_admits(dk: int, dv: int, on_tpu: bool) -> bool:
    """The flash kernels' rule on a head's widths. Interpreted (off TPU)
    any widths run. On TPU a [t, d] operand is tiled in lanes of 128: the
    value width, which is the width of the accumulator and of the output
    block, is 64 or lane-aligned; the key width is only ever contracted
    over (K Qᵀ) or the sublane side of dQᵀ, so a multiple of 64 does —
    192 = 128 + 64 of a latent-attention head runs as it is: 64 heads of
    t 8192, forward + backward, 43.5 ms a call on one v5e against 46.2 with
    q and k zero-padded to 256 in the door (the pads and slices cost more
    than the narrower operands save; 41.7 were they born 256 wide; PERF.md
    section 6, PR 33)."""
    if not on_tpu:
        return True
    return (dv == 64 or dv % 128 == 0) and (dk == dv or dk % 64 == 0)


def choose_impl(impl: str, b: int, t: int, d, masked: bool,
                seq_axis: Optional[str] = None, h: int = 1) -> str:
    """'ring' | 'blockwise' | 'flash' | 'sdpa' for self-attention over
    [b, h, t, .] heads whose keys are dk and whose values dv wide — `d` is
    the pair (dk, dv), or one int for both: a rule on what the call site
    can see — the layer's request (`attention_impl`), an active sequence
    axis, the backend, the shapes and the ambient mesh — and nothing else.
    There is no compile probe: a shape this rule admits and Mosaic refuses
    fails the step's compile with the kernel's name (which carries the
    shape) in the error.

    A sequence axis means ring attention whatever was asked for. 'auto'
    admits the flash kernels on TPU only, from t >= 512 (below that XLA's
    materialized-scores path holds while the scores fit on-chip; set by
    builder A/Bs in July, no driver number — ROADMAP S3); an explicit
    'pallas' skips the backend and length gates (CPU tests run it
    interpreted). Shape rule: no key-padding mask, block-aligned t, and
    head widths `_kernel_admits` (dv 64 or lane-aligned; dk = dv, or a
    multiple of 64). Mesh rule: the kernel runs per batch shard
    (kernel_call.per_batch_shard): 'auto' declines when the batch does not
    split evenly or the mesh shards anything else; an explicit request
    there raises in per_batch_shard.

    Where a kernel was wanted (the gates passed) and only the head widths
    decline it, the fallback is `sdpa`'s materialised [b, h, t, t] float32
    scores: beyond `SDPA_SCORE_BYTES` of them this raises, with the shape,
    rather than hand XLA an array no chip holds."""
    dk, dv = _head_widths(d)
    if seq_axis is not None:
        return "ring"
    if impl == "blockwise":
        return "blockwise"
    if impl not in ("pallas", "auto"):
        return "sdpa"
    auto = impl == "auto"
    on_tpu = jax.default_backend() == "tpu"
    if auto and not (pk.helpers_enabled() and on_tpu and t >= 512):
        return "sdpa"
    if masked or not (t <= 128 or t % 128 == 0):
        return "sdpa"
    if not _kernel_admits(dk, dv, on_tpu):
        if 4 * b * h * t * t > SDPA_SCORE_BYTES:
            raise ValueError(
                f"attention over q, k [{b}, {h}, {t}, {dk}], v [.., {dv}]: the flash "
                f"kernels admit a value width of 64 or a multiple of 128 and a key "
                f"width equal to it or a multiple of 64, and the materialised "
                f"float32 scores [{b}, {h}, {t}, {t}] are "
                f"{4 * b * h * t * t / 2 ** 30:.1f} GiB; pad the head or request "
                f"attention_impl='blockwise'")
        return "sdpa"
    if auto and not kernel_call.per_device_batch(b):
        return "sdpa"
    return "flash"


def attend(q, k, v, *, causal: bool, mask: Optional[jnp.ndarray] = None,
           impl: str = "auto", block_size: int = 512,
           window: Optional[int] = None) -> jnp.ndarray:
    """Self-attention o [b, h, t, dv] of q, k [b, h, t, dk] and v
    [b, h, t, dv] by the implementation `choose_impl` names: everything
    between a layer's heads and its output projection, scaled by
    dk^-0.5. `impl` is the layer's `attention_impl`, `mask` its [b, t]
    key-padding mask; `block_size` chunks the keys of the ring and
    blockwise recurrences; `window` (with `causal`): the keys a query
    looks back over, its own among them — None, or t and more: all."""
    from deeplearning4j_tpu.ops import ring  # ring.py builds on this module

    b, h, t, dk = q.shape
    window = None if window is None else pk.band(window, causal, t)
    axis = ring.active_sequence_axis()
    how = choose_impl(impl, b, t, (dk, v.shape[-1]), mask is not None, axis, h)
    if how == "ring":
        return ring.ring_attention_sharded(
            q, k, v, axis_name=axis, mask=mask, causal=causal,
            block_size=block_size, window=window)
    if how == "blockwise":
        return blockwise(q, k, v, mask=mask, causal=causal,
                         block_size=block_size, window=window)
    if how == "flash":
        bq, bk = pk.pick_flash_blocks(t, dk, q.dtype)
        interpret = kernel_call.interpret()
        return kernel_call.per_batch_shard(
            lambda q_, k_, v_: pk.flash_attention(q_, k_, v_, causal, None,
                                                  bq, bk, interpret, window),
            (q, k, v), (True, True, True))
    return sdpa(q, k, v, mask=mask, causal=causal, window=window)


def band_fill(t: int, dk: int, dtype, window: Optional[int]):
    """(score elements inside the causal band of `window` keys, score
    elements in the blocks the flash kernels' forward visits for it) of one
    head over t tokens, by the plan `attend` would give the call
    (`pk.pick_flash_blocks`, `pk.flash_visits`); a length the plan does not
    divide is visited whole, as `sdpa` does. A function of shapes alone."""
    w = t if window is None else min(window, t)
    band = w * (w + 1) // 2 + (t - w) * w
    if not (t <= 128 or t % 128 == 0):
        return band, t * t
    bq, bk = pk.pick_flash_blocks(t, dk, dtype)
    bq, bk = min(bq, t), min(bk, t)
    visits = pk.flash_visits(t, bq, bk, True, None if w >= t else w)["q_major"]
    return band, len(visits) * bq * bk


def rope_impl(impl: str, b: int, t: int, d: int, rot: int, dtype) -> str:
    """'pallas' | 'xla' for the half-split rotation of `rot` features of
    d-wide heads over t tokens with the split into heads: the kernels take
    bfloat16 or float32, heads of whole lane tiles (a multiple of 128), t a
    multiple of 16, a turned part within one lane tile or of whole tiles
    whose half is whole tiles too (`rope_kernels.fits`). 'auto' wants a TPU
    backend with the helpers on and rows that split evenly over an ambient
    data mesh; an explicit 'pallas' skips those gates (`pk.which`)."""
    return pk.which(impl, rope_kernels.fits(t, d, rot, dtype), b)


def rope_heads(a, heads, turned, d: int, rot: int, theta: float, impl: str = "auto",
               scaling: Optional[dict] = None):
    """The columns of a [b, t, sum(heads) d] — a projection's output as it
    leaves the product, or a part of it behind a norm — as one array
    [b, heads[p], t, d] a part, the first `rot` features of every head of a
    part with `turned[p]` rotated by its position (`hybrid.rotary`'s
    half-split pairing from feature 0; `scaling`: its frequency schedule,
    `rope_kernels.frequencies`), in ONE pass a direction through the
    kernel pair `dl4j_rope_fwd` / `dl4j_rope_bwd` — or None where
    `rope_impl` declines and the caller keeps its XLA form. Under a data mesh
    each device runs its own rows."""
    b, t, _ = a.shape
    if rope_impl(impl, b, t, d, rot, a.dtype) != "pallas":
        return None
    interpret = kernel_call.interpret()
    return kernel_call.per_batch_shard(
        lambda a_: rope_kernels.rope_split_kernels(
            a_, tuple(heads), tuple(turned), d, rot, theta, interpret,
            None if scaling is None else tuple(sorted(scaling.items()))), (a,), (True,))
