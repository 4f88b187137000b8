"""Fused blocked linear + softmax-cross-entropy pallas kernel.

Role (SURVEY.md §2.3 accelerator-helper layer): the transformer profile
(docs/PROFILE_TRANSFORMER.md) names the vocab-head loss as the top
non-gemm sink — the [b·t, V] logits are written in f32, re-read for the
log-softmax normalizer, and re-expanded in the backward, all at HBM
speed (≈1.3 ms of a 17.8 ms step at V=8192). This kernel computes

    per_row = T·logsumexp(z) − Σ_v t_v·z_v,   z = x @ W + b,  T = Σ_v t_v

without EVER materializing z in HBM: the vocab axis streams through VMEM
in blocks with an online (flash-style) logsumexp. The backward recomputes
z blockwise (two kernels: dx accumulates over vocab blocks, dW/db over
row blocks) — one extra MXU gemm each, traded for the eliminated
read-modify-write of [N, V] f32 logits and dlogits.

Label traffic is the second sink: a dense one-hot [N, V] f32 read costs
as much as a logits pass. The forward therefore detects one-hot rows
online while it reads the labels anyway (Σt = 1 ∧ Σt² = 1 ⟹ one-hot
for t ≥ 0) and records each row's target index; when EVERY row is
one-hot (the LM training case) the backward switches — via lax.cond on
the runtime flag, so soft labels (e.g. smoothing) stay exact through the
dense fallback kernels — to index-based kernels that rebuild the one-hot
from a [N] int32 vector and touch no [N, V] label bytes at all.

Reference role parity: the cuDNN-helper pattern (ConvolutionLayer.java:
74-84 discovery + fallthrough); the builtin path remains
`losses.compute` on XLA. `fused_linear_xent` is the one entry the output
layer calls: gate, shape rule (`plan`), mesh rule, per-shard call.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import kernel_call
from deeplearning4j_tpu.ops import linear as ops
from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.util import envflags
from deeplearning4j_tpu.util.cotangent import zeros_cotangent

# leave room for double-buffered streamed blocks (same budget philosophy
# as pallas_kernels.pick_lstm_block)
_VMEM_BUDGET = 12 * 1024 * 1024


def xent_helper_enabled() -> bool:
    """On when the pallas helper layer is on (TPU default); override with
    DL4J_TPU_PALLAS_XENT=1/0 (normalized spellings — same truthy/falsy
    set as lstm_helper_mode, via util.envflags)."""
    env = envflags.flag("DL4J_TPU_PALLAS_XENT")
    if env is not None:
        return env
    return pk.helpers_enabled()


def _pick(n, d, v, ew, bn_pref, bv_pref, labels: bool, dz_out: bool):
    """Largest-preference (bn, bv) whose working set fits the budget.
    Budget terms: x block, double-buffered W (+labels when read), the f32
    z/p intermediates, the dz spill blocks when emitted, the dx
    accumulator."""
    for bn in bn_pref:
        if n % bn:
            continue
        for bv in bv_pref:
            if v % bv:
                continue
            use = (bn * d * ew + 2 * d * bv * ew
                   + (2 * bn * bv * 4 if labels else 0)
                   + 2 * bn * bv * 4
                   + (2 * bn * bv * ew if dz_out else 0)
                   + bn * d * 4 + v * 4)
            if use <= _VMEM_BUDGET:
                return bn, bv
    return None


def plan(n: int, d: int, v: int, dtype) -> Optional[tuple]:
    """Per-phase block sizes ((fwd), (bwd_idx), (bwd_dense)), or None when
    the shape is out of regime: the kernels need TPU-tileable blocks that
    divide N and V, a lane-aligned contracting axis, and a vocab wide
    enough that skipping the logits round-trip beats XLA's fused
    reduction (V >= 2048 — below that the [N, V] tensors ride XLA fusion
    well enough that the builtin path wins). Cut and preferences are from
    a builder's sweep before the benchmark at N=8192, D=512, V=8192 (no
    driver number): the fwd wants the biggest row block
    that coexists with label blocks; the idx backward reads no labels, so
    it doubles the row block again to halve the serial W re-streams."""
    if v < 2048 or d % 128 != 0 or n % 8 != 0:
        return None
    ew = 2 if dtype == jnp.bfloat16 else 4
    bns = (512, 256, 128, 64, 32, 16, 8)
    fwd = _pick(n, d, v, ew, bns, (1024, 512, 256, 128), True, False)
    # backward blocks are deliberately a notch below what compiles
    # standalone: embedded in the full train step, Mosaic's scoped-vmem
    # accounting for the dz-spill kernels runs ~1.5-2x this module's
    # additive model (a (1024, 512) idx kernel and a (512, 512) dense
    # kernel both hit 17.04M against the 16M cap in-step after passing
    # standalone), so the idx path caps its row block at 512 and the
    # dense (soft-label fallback, speed-noncritical) path at 256
    bwd_idx = _pick(n, d, v, ew, bns, (512, 256, 128), False, True)
    bwd_dense = _pick(n, d, v, ew, bns[1:], (512, 256, 128), True, True)
    if not (fwd and bwd_idx and bwd_dense):
        return None
    return fwd, bwd_idx, bwd_dense


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, w_ref, b_ref, t_ref,
                row_ref, lse_ref, ts_ref, idx_ref, oh_ref,
                m_sc, s_sc, tz_sc, tsum_sc, t2_sc, bt_sc, bi_sc, *, nv: int,
                bv: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, -1e30)
        s_sc[:] = jnp.zeros_like(s_sc)
        tz_sc[:] = jnp.zeros_like(tz_sc)
        tsum_sc[:] = jnp.zeros_like(tsum_sc)
        t2_sc[:] = jnp.zeros_like(t2_sc)
        bt_sc[:] = jnp.full_like(bt_sc, -1.0)
        bi_sc[:] = jnp.zeros_like(bi_sc)

    z = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.float32)
    z = z + b_ref[:].astype(jnp.float32)
    t = t_ref[:].astype(jnp.float32)
    m_prev = m_sc[:]
    m_new = jnp.maximum(m_prev, jnp.max(z, axis=-1, keepdims=True))
    s_sc[:] = (s_sc[:] * jnp.exp(m_prev - m_new)
               + jnp.sum(jnp.exp(z - m_new), axis=-1, keepdims=True))
    m_sc[:] = m_new
    tz_sc[:] += jnp.sum(t * z, axis=-1, keepdims=True)
    tsum_sc[:] += jnp.sum(t, axis=-1, keepdims=True)
    t2_sc[:] += jnp.sum(t * t, axis=-1, keepdims=True)
    # online argmax of the labels: the target column for one-hot rows
    blk_max = jnp.max(t, axis=-1, keepdims=True)
    cols = lax.broadcasted_iota(jnp.int32, t.shape, 1)
    blk_arg = jnp.max(jnp.where(t >= blk_max, cols, 0), axis=-1,
                      keepdims=True) + j * bv
    better = blk_max > bt_sc[:]
    bi_sc[:] = jnp.where(better, blk_arg, bi_sc[:])
    bt_sc[:] = jnp.where(better, blk_max, bt_sc[:])

    @pl.when(j == nv - 1)
    def _():
        lse = m_sc[:] + jnp.log(s_sc[:])
        lse_ref[:] = lse
        ts_ref[:] = tsum_sc[:]
        row_ref[:] = tsum_sc[:] * lse - tz_sc[:]
        idx_ref[:] = bi_sc[:]
        one = ((jnp.abs(tsum_sc[:] - 1.0) < 1e-4)
               & (jnp.abs(t2_sc[:] - 1.0) < 1e-4)
               & (jnp.abs(bt_sc[:] - 1.0) < 1e-4))
        oh_ref[:] = one.astype(jnp.float32)


def _fwd(x, w, b2, t, bn: int, bv: int, interpret: bool):
    n, d = x.shape
    v = w.shape[1]
    nn, nv = n // bn, v // bv
    f32 = jnp.float32
    col = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, nv=nv, bv=bv),
        grid=(nn, nv),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),
            pl.BlockSpec((1, bv), lambda i, j: (0, j)),
            pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
        ],
        out_specs=[col, col, col, col, col],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), f32),  # per-row loss
            jax.ShapeDtypeStruct((n, 1), f32),  # logsumexp residual
            jax.ShapeDtypeStruct((n, 1), f32),  # T = sum(labels) residual
            jax.ShapeDtypeStruct((n, 1), jnp.int32),  # argmax(labels)
            jax.ShapeDtypeStruct((n, 1), f32),  # 1.0 when row is one-hot
        ],
        scratch_shapes=([pltpu.VMEM((bn, 1), f32) for _ in range(6)]
                        + [pltpu.VMEM((bn, 1), jnp.int32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=pk.kernel_name("xent_fwd", x.dtype, n=n, d=d, v=v, bn=bn, bv=bv),
        interpret=interpret,
    )(x, w, b2, t)


# ---------------------------------------------------------------------------
# backward — dense-label variants (exact for soft labels)
# ---------------------------------------------------------------------------


def _dz_dense(x_ref, w_ref, b_ref, t_ref, lse_ref, ts_ref, g_ref):
    """Recompute this block's dz = (softmax(z)·T − t) · g in f32."""
    z = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.float32)
    z = z + b_ref[:].astype(jnp.float32)
    p = jnp.exp(z - lse_ref[:])
    t = t_ref[:].astype(jnp.float32)
    return (p * ts_ref[:] - t) * g_ref[:]


def _dz_idx(x_ref, w_ref, b_ref, idx_ref, lse_ref, g_ref, col0):
    """dz for one-hot labels rebuilt from the target index — no [N, V]
    label bytes: onehot(idx) via an iota compare (T = 1)."""
    z = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.float32)
    z = z + b_ref[:].astype(jnp.float32)
    p = jnp.exp(z - lse_ref[:])
    cols = lax.broadcasted_iota(jnp.int32, p.shape, 1) + col0
    t = (cols == idx_ref[:]).astype(jnp.float32)
    return (p - t) * g_ref[:]


def _bwd_kernel(x_ref, w_ref, b_ref, t_ref, lse_ref, ts_ref, g_ref,
                dx_ref, dz_ref, db_ref, acc_sc, db_sc, *, nn: int, nv: int,
                bv: int, use_idx: bool):
    """One pass per (row-block, vocab-block): recompute z ONCE, spill dz
    (in dz_ref's dtype, bf16 on the mixed path) for the XLA wgrad gemm,
    accumulate dx in scratch and db in a full-width [1, V] f32 scratch
    (V f32 is KBs — the one full-vocab buffer that DOES fit VMEM)."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_sc[:] = jnp.zeros_like(acc_sc)

    @pl.when((i == 0) & (j == 0))
    def _():
        db_sc[:] = jnp.zeros_like(db_sc)

    if use_idx:
        dz = _dz_idx(x_ref, w_ref, b_ref, t_ref, lse_ref, g_ref, j * bv)
    else:
        dz = _dz_dense(x_ref, w_ref, b_ref, t_ref, lse_ref, ts_ref, g_ref)
    dz_ref[:] = dz.astype(dz_ref.dtype)
    # dz [bn, bv] · Wᵀ — contract the vocab axis
    acc_sc[:] += lax.dot_general(
        dz, w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    db_sc[:, pl.ds(j * bv, bv)] += jnp.sum(dz, axis=0, keepdims=True)

    @pl.when(j == nv - 1)
    def _():
        dx_ref[:] = acc_sc[:].astype(dx_ref.dtype)

    @pl.when((i == nn - 1) & (j == nv - 1))
    def _():
        db_ref[:] = db_sc[:]


def _bwd(x, w, b2, t_or_idx, lse, ts, g, bn: int, bv: int, interpret: bool,
         use_idx: bool):
    """dz-spill backward: one kernel recomputes z once per block and emits
    dx + db + the dz spill; dW is a single XLA MXU gemm over the spilled
    dz. On the mixed-precision path the spill is bf16 — the same dz dtype
    the builtin path's cast-transpose feeds its wgrad gemm, so numerics
    stay in the builtin's class while dz HBM traffic halves vs f32
    dlogits. `t_or_idx` is the dense [N, V] labels (use_idx=False) or the
    [N, 1] int32 target indices (use_idx=True, zero label bytes)."""
    n, d = x.shape
    v = w.shape[1]
    nn, nv = n // bn, v // bv
    col = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))
    t_spec = (col if use_idx
              else pl.BlockSpec((bn, bv), lambda i, j: (i, j)))
    dz_dt = jnp.bfloat16 if x.dtype == jnp.bfloat16 else jnp.float32
    dx, dz, db = pl.pallas_call(
        functools.partial(_bwd_kernel, nn=nn, nv=nv, bv=bv, use_idx=use_idx),
        grid=(nn, nv),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),
            pl.BlockSpec((1, bv), lambda i, j: (0, j)),
            t_spec, col, col, col,
        ],
        out_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
            pl.BlockSpec((1, v), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x.dtype),
            jax.ShapeDtypeStruct((n, v), dz_dt),
            jax.ShapeDtypeStruct((1, v), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bn, d), jnp.float32),
                        pltpu.VMEM((1, v), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=pk.kernel_name("xent_bwd_idx" if use_idx else "xent_bwd_dense",
                    x.dtype, n=n, d=d, v=v, bn=bn, bv=bv),
        interpret=interpret,
    )(x, w, b2, t_or_idx, lse, ts, g)
    # xᵀ [d, n] · dz [n, v] on the MXU — the one materialized [N, V]
    # tensor left in the fused stage, at half the builtin's f32 width
    dw = lax.dot_general(x, dz, (((0,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)
    return dx, dw.astype(w.dtype), db


# ---------------------------------------------------------------------------
# custom-vjp surface
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def linear_xent_rows(x, w, b, labels, blocks: tuple,
                     interpret: bool = False):
    """per_row [N] f32 of softmax cross-entropy through the linear head,
    logits never materialized. `blocks` is plan()'s per-phase tuple.
    labels may be one-hot or soft (row sums scale the logsumexp term);
    all-one-hot batches take a backward with zero [N, V] label traffic.
    Gradients flow to x, w, b; labels are treated as data (zero cotangent
    — the standard training contract)."""
    (bn, bv), _, _ = blocks
    per_row, _, _, _, _ = _fwd(x, w, b.reshape(1, -1), labels, bn, bv,
                               interpret)
    return per_row[:, 0]


def _vjp_fwd(x, w, b, labels, blocks, interpret):
    (bn, bv), _, _ = blocks
    b2 = b.reshape(1, -1)
    per_row, lse, ts, idx, oh = _fwd(x, w, b2, labels, bn, bv, interpret)
    return per_row[:, 0], (x, w, b2, labels, lse, ts, idx,
                           jnp.min(oh) > 0.5)


def _vjp_bwd(blocks, interpret, res, g):
    _, (bni, bvi), (bnd, bvd) = blocks
    x, w, b2, labels, lse, ts, idx, all_onehot = res
    g2 = g.astype(jnp.float32).reshape(-1, 1)

    def idx_path(_):
        return _bwd(x, w, b2, idx, lse, ts, g2, bni, bvi, interpret, True)

    def dense_path(_):
        return _bwd(x, w, b2, labels, lse, ts, g2, bnd, bvd, interpret,
                    False)

    dx, dw, db = lax.cond(all_onehot, idx_path, dense_path, None)
    # labels are data, never trained — but integer-dtype labels demand a
    # float0 cotangent, not a same-dtype zeros array (ADVICE.md r5)
    return dx, dw, db[0].astype(b2.dtype), zeros_cotangent(labels)


linear_xent_rows.defvjp(_vjp_fwd, _vjp_bwd)


def fused_linear_xent(x, w, b, labels):
    """Per-example softmax cross-entropy of the linear head x [b, .., d] @
    w [d, v] (+ b, or None) against dense labels [b, .., v] through the
    fused kernel — scores [b, ..], the logits never in HBM — or None when
    it is not admitted here and the caller keeps `losses.compute`: the
    gate, agreeing shapes, a float32 or bf16 head under the mixed policy,
    a `plan` for the rows ONE device sees. Under a data mesh each device
    runs the kernel on its own rows (GSPMD would gather the batch around
    the custom call); a mesh that shards anything else declines."""
    if (not xent_helper_enabled()
            or w is None or jnp.ndim(w) != 2 or jnp.ndim(labels) < 2
            or x.shape[-1] != w.shape[0] or labels.shape[-1] != w.shape[1]
            or x.shape[:-1] != labels.shape[:-1]):
        return None
    xc, wc = ops._mixed_cast(x, w)
    if xc.dtype not in (jnp.float32, jnp.bfloat16):
        return None
    n = math.prod(x.shape[:-1])
    b_dev = kernel_call.per_device_batch(x.shape[0])
    if not b_dev:
        return None
    p = plan(n // x.shape[0] * b_dev, wc.shape[0], wc.shape[1], xc.dtype)
    if p is None:
        return None
    if b is None:
        b = jnp.zeros((wc.shape[1],), jnp.float32)
    interpret = kernel_call.interpret()
    per_row = kernel_call.per_batch_shard(
        lambda x_, w_, b_, t_: linear_xent_rows(x_, w_, b_, t_, p, interpret),
        (xc.reshape(n, xc.shape[-1]), wc, b,
         labels.reshape(n, labels.shape[-1])),
        (True, False, False, True))
    return per_row.reshape(labels.shape[:-1])


def linear_xent_reference(x, w, b, labels):
    """XLA reference formulation (equivalence tests and the A/B baseline):
    the exact math of losses.compute's fused log-softmax mcxent path,
    per row."""
    z = jnp.dot(x, w, preferred_element_type=jnp.float32)
    z = z + b.astype(jnp.float32)
    logp = jax.nn.log_softmax(z, axis=-1)
    return -jnp.sum(labels.astype(jnp.float32) * logp, axis=-1)
