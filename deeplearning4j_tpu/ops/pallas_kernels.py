"""Pallas TPU kernels — the accelerator-helper layer.

Role parity with deeplearning4j-cuda (SURVEY.md §2.3): the reference loads
cuDNN helpers reflectively per layer (ConvolutionLayer.java:74-84) and falls
through to the builtin path when absent. Here the "builtin path" is already
XLA (which fuses conv/BN/elementwise well on its own — no kernel needed),
so pallas earns its keep only where XLA's generic lowering leaves time on
the table:

  flash_attention — fused causal/masked attention: one program per
      (batch·head, q-block) — or per batch·head at short t — online
      softmax in VMEM, K/V streamed block by block; only the blocks the
      diagonal crosses are masked, none that lies in the future is
      visited. With a window — query i sees the `window` keys
      i - window + 1 .. i: 512 keys, the query's own among them — the
      walk also STARTS at the first block the band touches and the
      blocks the window's edge crosses take the same mask; the
      kernel's name then carries `w<window>`. O(t) memory like
      ops.attention.blockwise but without materializing per-block
      intermediates in HBM; the cuDNN-fused-softmax-attention analogue.
  lstm_scan — the fused recurrent loop (cudnnRNNForwardTraining's role):
      input projections are pre-computed as one big gemm outside (XLA);
      this kernel runs ALL timesteps with h/c resident in VMEM, one
      [b, n]x[n, 4n] MXU gemm per step, eliminating per-step HLO-loop
      overhead.

Backward passes are fused pallas kernels too (round 3): the LSTM bwd runs
the dh/dc recurrence with cell states recomputed into VMEM scratch
(cudnnRNNBackwardData/Weights role, CudnnLSTMHelper.java:612), and the
flash bwd rebuilds P blockwise from the saved logsumexp, once a block, in
one kernel per k-block that also adds up dQ (PR 30). Numerics match the
XLA formulations (CuDNNGradientChecks-pattern equivalence tests); an
over-VMEM-budget LSTM bwd falls back to the XLA-recompute vjp.

Admission: each family has ONE entry function that owns its gate, shape
and mesh rules, block plan, per-shard mapping and fallback, and the layers
call nothing else — `ops.attention.attend` for the flash kernels,
`fused_lstm` and `fused_affine_act` here, `xent_kernel.fused_linear_xent`.
On by default on TPU backends, off on CPU (where `interpret=True` would be
slower than XLA); override with DL4J_TPU_PALLAS=1/0. The full-resident
LSTM kernels are additionally OPT-IN via DL4J_TPU_PALLAS_LSTM=1, bn_act
via DL4J_TPU_PALLAS_CONVBN=1. Admission is a rule on shapes, dtypes, the
backend and the ambient mesh — never a trial compile: a kernel Mosaic
refuses fails the enclosing step's compile, and every pallas_call is
named for its family and shape (`kernel_name`) so the error, the HLO and a
profiler trace all say which call it was. Under a device mesh each kernel
runs per batch shard (ops/kernel_call.py); GSPMD cannot partition it.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import kernel_call
from deeplearning4j_tpu.util import envflags
from deeplearning4j_tpu.util.cotangent import zeros_cotangent
from deeplearning4j_tpu.util.jaxcompat import REMAT_KEEP

NEG_INF = -1e30


def kernel_name(family: str, dtype, **dims) -> str:
    """Kernel name for a pallas_call: family, the dims that fix its block
    plan, dtype — e.g. dl4j_flash_fwd_bh128_t512_d64_bq512_bk512_bfloat16.
    It is the custom call's kernel_name in the HLO, the event name in a
    profiler trace, and what a Mosaic refusal is reported under."""
    parts = "_".join(f"{k}{v}" for k, v in dims.items())
    return f"dl4j_{family}_{parts}_{jnp.dtype(dtype).name}"


def helpers_enabled() -> bool:
    env = envflags.flag("DL4J_TPU_PALLAS")
    if env is not None:
        return env
    return jax.default_backend() == "tpu"


def which(impl: str, fits: bool, rows: int) -> str:
    """'pallas' where a kernel family fits the operands and `impl` asks for
    it: 'auto' also wants a TPU backend with the helpers on and rows that
    split evenly over an ambient data mesh; an explicit 'pallas' skips those
    gates (the CPU tests run the kernels interpreted)."""
    if impl == "auto":
        fits = (fits and helpers_enabled() and jax.default_backend() == "tpu"
                and bool(kernel_call.per_device_batch(rows)))
    return "pallas" if fits and impl in ("auto", "pallas") else "xla"


def lstm_helper_mode() -> str:
    """Tri-state DL4J_TPU_PALLAS_LSTM: 'forced' (truthy — both kernel
    families admitted wherever their plans fit), 'off' (set falsy — both
    families disabled, the LSTM-specific kill switch that leaves
    flash/xent helpers alone), 'auto' (unset — chunked kernels in
    `chunked_lstm_auto_regime` only). The full-t resident kernels are
    opt-in because a builder's A/B from before the benchmark (no driver
    number) had XLA's lax.scan ahead of them at every shape tried."""
    # only recognised truthy spellings force the kernels on;
    # "0"/"false"/"no"/garbage all mean OFF (envflags spelling contract)
    return envflags.mode("DL4J_TPU_PALLAS_LSTM")


# ============================================================ flash attention
_SCOPED_VMEM_DEFAULT = 16 * 2 ** 20   # what Mosaic gives a kernel unasked (v5e)
_VMEM_CEILING = 110 * 2 ** 20         # of the chip's 128 MiB

def _lanes(*widths: int) -> int:
    """Columns that [., d] operands of these widths occupy in VMEM
    together: each in whole tiles of 128."""
    return sum(-(-d // 128) * 128 for d in widths)


def _flash_vmem(t: int, columns: int, dtype, *, rows: int, scores: int) -> dict:
    """`compiler_params` for a flash kernel that keeps resident,
    double-buffered, [t, .] operands or results of `columns` columns in
    all (`_lanes` of their widths) and `rows` float32 row statistics
    [1, t] (a sublane-padded [8, t] tile each), beside the float32
    temporaries of one step over `scores` score elements (S, P, dP, dS).
    Nothing — Mosaic's own scoped limit — while that fits it with room to
    spare (t 1024, head 64: 5 MiB); a raised limit for a long sequence of
    wide heads (t 8192, head 256: 16 MiB of K and V alone), which the
    default refuses at compile time."""
    need = (2 * (columns * t * jnp.dtype(dtype).itemsize + rows * 8 * t * 4)
            + 4 * scores * 4)
    if need <= 3 * _SCOPED_VMEM_DEFAULT // 4:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(need + _SCOPED_VMEM_DEFAULT, _VMEM_CEILING))}


def _both(x, n):
    return (jnp.int32(a) if isinstance(a, int) else a for a in (x, n))


def _at_most(x, n):
    """min(x, n) for Python ints or traced scalars."""
    if isinstance(x, int) and isinstance(n, int):
        return min(x, n)
    return lax.min(*_both(x, n))


def _at_least(x, n):
    """max(x, n) for Python ints or traced scalars."""
    if isinstance(x, int) and isinstance(n, int):
        return max(x, n)
    return lax.max(*_both(x, n))


def _q_major_bounds(qi, bq: int, bk: int, nk: int, causal: bool,
                    window: Optional[int] = None):
    """Key blocks that the forward walks for q block `qi`: (start, edge end,
    first masked, end). Without a window start and edge end are 0: blocks
    [0, first masked) lie wholly on the visible side of the diagonal —
    their last key is no later than the block's first query — and take no
    mask; [first masked, end) are the ones the diagonal crosses; from `end`
    on every key is later than the block's last query and nothing is
    visited. With a window (query i sees the `window` keys i - window + 1
    .. i) the walk STARTS at the block that holds the first key the block's
    FIRST query still sees; [start, edge end) are the blocks the window's
    edge crosses — some query of the block is too late for some key of
    them — and take the mask as the diagonal's do; [edge end, first masked)
    lie wholly inside the band. Works on Python ints and on traced scalars
    alike (`flash_visits` and the kernels share it)."""
    if not causal:
        return 0, 0, nk, nk
    first_masked = (qi * bq) // bk
    end = _at_most(((qi + 1) * bq + bk - 1) // bk, nk)
    if window is None:
        return 0, 0, first_masked, end
    start = _at_least(qi * bq - (window - 1), 0) // bk
    # the first block whose first key the block's LAST query still sees
    inside = _at_least((qi + 1) * bq - window + bk - 1, 0) // bk
    return start, _at_most(_at_least(inside, start), first_masked), first_masked, end


def _k_major_bounds(kj, bq: int, bk: int, nq: int, causal: bool,
                    window: Optional[int] = None):
    """q blocks that the backward walks for key block `kj`: (start, first
    unmasked, edge start, end). Blocks before `start` end before the first
    key and are not visited; [start, first unmasked) are the ones the
    diagonal crosses; [first unmasked, edge start) see the whole key block;
    without a window edge start = end = nq. With one, [edge start, end) are
    the q blocks the window's edge crosses (their last query is too late
    for the block's first key) and from `end` on no query sees the block's
    last key."""
    if not causal:
        return 0, 0, nq, nq
    start = (kj * bk) // bq
    unmasked = _at_most(((kj + 1) * bk + bq - 1) // bq, nq)
    if window is None:
        return start, unmasked, nq, nq
    end = _at_most(((kj + 1) * bk + window - 2) // bq + 1, nq)
    edge = _at_most(_at_least((kj * bk + window) // bq, unmasked), end)
    return start, unmasked, edge, end


def flash_visits(t: int, bq: int, bk: int, causal: bool,
                 window: Optional[int] = None) -> dict:
    """Which (q block, key block) pairs each kernel visits, and whether it
    masks them: {"q_major": [(qi, kj, masked), ...] (the forward, which
    walks key blocks for a q block), "k_major": [...] (the backward,
    which walks q blocks for a key block)} — the kernels' own loop
    bounds, spelled out for the tests."""
    nq, nk = t // bq, t // bk
    q_major, k_major = [], []
    for qi in range(nq):
        start, edge, first_masked, end = _q_major_bounds(qi, bq, bk, nk, causal, window)
        q_major += [(qi, kj, kj < edge or kj >= first_masked) for kj in range(start, end)]
    for kj in range(nk):
        start, unmasked, edge, end = _k_major_bounds(kj, bq, bk, nq, causal, window)
        k_major += [(qi, kj, qi < unmasked or qi >= edge) for qi in range(start, end)]
    return {"q_major": q_major, "k_major": k_major}


def _dot_nt(a, b):
    """a [m, k] · b [n, k]ᵀ -> float32 [m, n]; the MXU takes the second
    operand transposed as it loads it, no transposed copy is built."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _walk(whole: bool, lo, hi, blk: int, fn, carry):
    """carry = fn(carry, offset, width) over the blocks [lo, hi) of `blk`
    rows. A q or key block a program (bounds traced): a fori_loop, one
    block a step. A whole head a program (bounds static): ONE step over
    the whole range — no loop, no rescale between blocks, every offset a
    constant."""
    if whole:
        return fn(carry, lo * blk, (hi - lo) * blk) if hi > lo else carry
    return lax.fori_loop(
        lo, hi, lambda j, c: fn(c, pl.multiple_of(j * blk, blk), blk), carry)


def _causal_keep(rows: int, cols: int, row0, col0, transposed=False,
                 window: Optional[int] = None):
    """[rows, cols] bool: query row0 + r sees key col0 + c — or, transposed
    (rows are keys, columns queries), key row0 + r is seen by query
    col0 + c. A query i sees key j where 0 <= i - j, and with a window
    where i - j < window too."""
    rel = (lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
           - lax.broadcasted_iota(jnp.int32, (rows, cols), 1))
    off = col0 - row0
    if window is None:
        return rel <= off if transposed else rel >= off
    if transposed:
        return (rel <= off) & (rel > off - window)
    return (rel >= off) & (rel < off + window)


def _whole_head(t: int, blk: int) -> bool:
    """A head is ONE program — its blocks of `blk` rows unrolled, every
    bound static, the unmasked part of a block's row of scores one step —
    while that stays a small program (at most 8 blocks) whose widest step
    of float32 scores stays small ([blk, t] within 2 MiB); else a block a
    program with loops inside. 96 heads of t 1024 and head 64 in blocks
    of 256, forward + dQ + dK/dV a call: 1.00 ms as ONE program a head,
    2.31 as a block a program (PERF.md section 6, PR 30)."""
    return t // blk <= 8 and blk * t <= 2 ** 19


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref=None, *, bq: int,
                      bk: int, whole: bool, causal: bool, scale: float,
                      window: Optional[int] = None):
    """One (batch·head, q-block) program — q_ref [bq, dk] — or one
    batch·head program that walks its q blocks itself — q_ref [t, dk];
    k_ref [t, dk], v_ref [t, dv] (the value width may differ from the key
    width; o_ref is as wide as v). lse_ref (backward-support variant):
    per-row logsumexp m + log(l), the statistic the blockwise backward
    needs to rebuild P without a second online softmax."""
    dv = v_ref.shape[1]
    nk = k_ref.shape[0] // bk

    def q_block(qi, rows):
        q = q_ref[rows, :] * scale

        def step(carry, k0, width, masked):
            m, l, acc = carry
            k_blk = k_ref[pl.ds(k0, width), :]
            v_blk = v_ref[pl.ds(k0, width), :]
            s = _dot_nt(q, k_blk)
            if masked:
                s = jnp.where(_causal_keep(bq, width, qi * bq, k0, window=window),
                              s, NEG_INF)
            # a row whose keys in an edge block are all outside its window
            # adds exp(0) here; its own diagonal block comes later and its
            # correction exp(NEG_INF - m) = 0 wipes that out
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1, keepdims=True)
            acc = acc * corr + jnp.dot(p.astype(v_blk.dtype), v_blk,
                                       preferred_element_type=jnp.float32)
            return m_new, l, acc

        carry = (jnp.full((bq, 1), NEG_INF, jnp.float32),
                 jnp.zeros((bq, 1), jnp.float32),
                 jnp.zeros((bq, dv), jnp.float32))
        start, edge, first_masked, end = _q_major_bounds(qi, bq, bk, nk, causal, window)
        if window is not None:
            carry = _walk(whole, start, edge, bk,
                          functools.partial(step, masked=True), carry)
        carry = _walk(whole, edge, first_masked, bk,
                      functools.partial(step, masked=False), carry)
        m, l, acc = _walk(whole, first_masked, end, bk,
                          functools.partial(step, masked=True), carry)
        o_ref[rows, :] = (acc / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)
        if lse_ref is not None:
            # a row [1, bq], the layout the backward reads: the column,
            # spread over the lanes, goes through one transpose
            lse = m + jnp.log(jnp.maximum(l, 1e-37))
            lse_ref[:, rows] = jnp.broadcast_to(lse, (bq, 128)).T[:1]

    if whole:
        for qi in range(q_ref.shape[0] // bq):
            q_block(qi, pl.ds(qi * bq, bq))
    else:
        q_block(pl.program_id(1), slice(None))


def _flash_widths(d: int, dv: int, window: Optional[int] = None) -> dict:
    """The head widths as a kernel's name carries them: `d` alone where
    keys and values are equally wide (the names every reader knows), else
    the key width `d` and the value width `dv`; then the window `w`, only
    where there is one (`.._d128_w512_..`: a trace tells the banded calls
    from the whole triangles by it)."""
    dims = {"d": d} if d == dv else {"d": d, "dv": dv}
    return dims if window is None else {**dims, "w": window}


def _flash_fwd(q, k, v, *, causal: bool, scale: float, bq: int, bk: int,
               interpret: bool, return_lse: bool = False,
               window: Optional[int] = None):
    b, h, t, d = q.shape
    dv = v.shape[-1]
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, t, d)
    vf = v.reshape(b * h, t, dv)
    whole = _whole_head(t, bq)
    rows = t if whole else bq
    grid = (b * h, t // rows)
    kernel = functools.partial(_flash_fwd_kernel, bq=bq, bk=bk, whole=whole,
                               causal=causal, scale=scale, window=window)
    out_shape = jax.ShapeDtypeStruct((b * h, t, dv), q.dtype)
    out_spec = pl.BlockSpec((None, rows, dv), lambda i, j: (i, j, 0))
    if return_lse:
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((b * h, 1, t), jnp.float32))
        out_spec = (out_spec,
                    pl.BlockSpec((None, 1, rows), lambda i, j: (i, 0, j)))
    got = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, rows, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, t, dv), lambda i, j: (i, 0, 0)),
        ],
        out_specs=out_spec,
        name=kernel_name("flash_fwd", q.dtype, bh=b * h, t=t,
                         **_flash_widths(d, dv, window), bq=bq, bk=bk),
        interpret=interpret,
        # K, V (+ q, o: a head a program)
        **_flash_vmem(t, _lanes(d, dv, *((d, dv) if whole else ())), q.dtype,
                      rows=int(return_lse), scores=bq * (t if whole else bk)),
    )(qf, kf, vf)
    if return_lse:
        out, lse = got
        return out.reshape(b, h, t, dv), lse.reshape(b, h, t)
    return got.reshape(b, h, t, dv)


def band(window: Optional[int], causal: bool, t: int) -> Optional[int]:
    """The window a call runs with: None where it reaches back over the
    whole sequence anyway (then the call IS the causal one, name, kernel
    and bits)."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError(f"window={window}: a window is a number of keys >= 1 "
                         f"that a query looks back over, its own among them; "
                         f"it needs causal=True")
    return None if window >= t else int(window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None, bq: int = 128,
                    bk: int = 128, interpret: bool = False,
                    window: Optional[int] = None):
    """Fused attention o = softmax(qkᵀ·scale)v over q, k [b, h, t, d] and
    v [b, h, t, dv] -> [b, h, t, dv] (dv = d for most models; a latent-
    attention head carries a positional part in its keys only). With a
    `window` query i sees the keys i - window + 1 .. i (`window` keys, its
    own among them) and the kernels visit the blocks that band touches and
    no other; a window of t or more is the causal call.

    t must divide by the block sizes (pad upstream); numerics match
    ops.attention.sdpa. Backward is one blockwise pallas kernel
    (_flash_bwd_kernel) rebuilding P from the logsumexp saved by the
    forward — O(t) memory in both directions."""
    s = (q.shape[-1] ** -0.5) if scale is None else scale
    bq = min(bq, q.shape[2])
    bk = min(bk, q.shape[2])
    return _flash_fwd(q, k, v, causal=causal, scale=s, bq=bq, bk=bk,
                      interpret=interpret, window=band(window, causal, q.shape[2]))


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dqt_ref, *, bq: int, bk: int,
                      whole: bool, causal: bool, scale: float,
                      window: Optional[int] = None):
    """The backward for one (batch·head, k-block) program — or for a whole
    batch·head, its key blocks walked here. P is rebuilt from the saved
    logsumexp ONCE a block and transposed from the start (rows are keys):
    Sᵀ = K Qᵀ [bk, bq], with the row statistics as rows (lse_ref,
    delta_ref [1, t]), dSᵀ = Pᵀ ∘ (V dOᵀ − Δ). Then dV = ΣPᵀ dO and
    dK = scale · ΣdSᵀ Q are plain products over the q blocks that attend
    to this k block, and dQᵀ = scale · Kᵀ dSᵀ adds up, over a head's key
    blocks, in the float32 scratch dqt_ref [d, t]; dq_ref [t, d] is
    written once a head. q, k, dq, dk are d wide, v, dO, dv may be another
    width."""
    t, d = q_ref.shape
    dv_ = v_ref.shape[1]
    nq = t // bq

    def k_block(kj, rows):
        k_blk = k_ref[rows, :]
        v_blk = v_ref[rows, :]

        def step(carry, q0, width, masked):
            dk, dv = carry
            at = pl.ds(q0, width)
            q = q_ref[at, :] * scale
            do = do_ref[at, :]
            pt = jnp.exp(_dot_nt(k_blk, q) - lse_ref[:, at])
            if masked:
                pt = jnp.where(_causal_keep(bk, width, kj * bk, q0, True, window),
                               pt, 0.0)
            dv = dv + jnp.dot(pt.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
            dst = (pt * (_dot_nt(v_blk, do) - delta_ref[:, at])).astype(q.dtype)
            # q carries the scale already, so dSᵀ q is dK
            dk = dk + jnp.dot(dst, q, preferred_element_type=jnp.float32)
            dqt_ref[:, at] += lax.dot_general(
                k_blk, dst, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dk, dv

        start, unmasked, edge, end = _k_major_bounds(kj, bq, bk, nq, causal, window)
        carry = _walk(whole, start, unmasked, bq,
                      functools.partial(step, masked=True),
                      (jnp.zeros((bk, d), jnp.float32),
                       jnp.zeros((bk, dv_), jnp.float32)))
        dk, dv = carry = _walk(whole, unmasked, edge, bq,
                               functools.partial(step, masked=False), carry)
        if window is not None:
            dk, dv = _walk(whole, edge, end, bq,
                           functools.partial(step, masked=True), carry)
        dk_ref[rows, :] = dk.astype(dk_ref.dtype)
        dv_ref[rows, :] = dv.astype(dv_ref.dtype)

    def head_done():
        dq_ref[...] = (dqt_ref[...].T * scale).astype(dq_ref.dtype)

    if whole:
        dqt_ref[...] = jnp.zeros_like(dqt_ref)
        for kj in range(t // bk):
            k_block(kj, pl.ds(kj * bk, bk))
        head_done()
    else:
        kj = pl.program_id(1)

        @pl.when(kj == 0)
        def _():
            dqt_ref[...] = jnp.zeros_like(dqt_ref)

        k_block(kj, slice(None))
        pl.when(kj == pl.num_programs(1) - 1)(head_done)


def _flash_bwd(q, k, v, o, lse, g, *, causal: bool, scale: float, bq: int,
               bk: int, interpret: bool, window: Optional[int] = None):
    b, h, t, d = q.shape
    dv = v.shape[-1]
    bh = b * h
    qf, kf = (a.reshape(bh, t, d) for a in (q, k))
    vf, dof = (a.reshape(bh, t, dv) for a in (v, g))
    # Δ = rowsum(dO ∘ O): cheap fused elementwise+reduce in XLA
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    whole = _whole_head(t, bk)
    krows = t if whole else bk

    def seq(w):
        return pl.BlockSpec((None, t, w), lambda i, j: (i, 0, 0))

    def kblk(w):
        return pl.BlockSpec((None, krows, w), lambda i, j: (i, j, 0))

    row = pl.BlockSpec((None, 1, t), lambda i, j: (i, 0, 0))
    dq, dk, dv_out = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, bq=bq, bk=bk, whole=whole,
                          causal=causal, scale=scale, window=window),
        out_shape=tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                        for a in (qf, kf, vf)),
        grid=(bh, t // krows),
        in_specs=[seq(d), kblk(d), kblk(dv), seq(dv), row, row],
        out_specs=(seq(d), kblk(d), kblk(dv)),
        scratch_shapes=[pltpu.VMEM((d, t), jnp.float32)],
        name=kernel_name("flash_bwd", q.dtype, bh=bh, t=t,
                         **_flash_widths(d, dv, window), bq=bq, bk=bk),
        interpret=interpret,
        # q, dO, dQ, the float32 dQᵀ (+ K, V, dK, dV: a head a program)
        **_flash_vmem(t, _lanes(d, dv, d, d, *((d, dv, d, dv) if whole else ())),
                      q.dtype, rows=2, scores=bk * (t if whole else bq)),
    )(qf, kf, vf, dof, lse.reshape(bh, 1, t), delta.reshape(bh, 1, t))
    return (dq.reshape(b, h, t, d), dk.reshape(b, h, t, d),
            dv_out.reshape(b, h, t, dv))


def _flash_vjp_fwd(q, k, v, causal, scale, bq, bk, interpret, window=None):
    s = (q.shape[-1] ** -0.5) if scale is None else scale
    bq_ = min(bq, q.shape[2])
    bk_ = min(bk, q.shape[2])
    out, lse = _flash_fwd(q, k, v, causal=causal, scale=s, bq=bq_, bk=bk_,
                          interpret=interpret, return_lse=True,
                          window=band(window, causal, q.shape[2]))
    # all the backward kernel needs beside q, k, v: kept by a block's 'full'
    # remat, whose recompute then does not call the forward kernel again
    out, lse = (checkpoint_name(a, REMAT_KEEP) for a in (out, lse))
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, bq, bk, interpret, window, res, g):
    q, k, v, o, lse = res
    s = (q.shape[-1] ** -0.5) if scale is None else scale
    bq_ = min(bq, q.shape[2])
    bk_ = min(bk, q.shape[2])
    return _flash_bwd(q, k, v, o, lse, g, causal=causal, scale=s, bq=bq_,
                      bk=bk_, interpret=interpret,
                      window=band(window, causal, q.shape[2]))


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ============================================================ fused LSTM scan
def _lstm_kernel(zx_ref, r_ref, *rest, t: int, time_major: bool = False,
                 peephole: bool = False, masked: bool = False):
    """One batch-block program: all timesteps with h/c in registers/VMEM.
    zx_ref [bb, t, 4n] (input projections + bias, gate order i,f,g,o) — or
    [t, bb, 4n] when time_major (the bf16 layout: Mosaic needs the dynamic
    per-step index on the OUTERMOST dim for sub-32-bit dtypes; a bf16
    batch-major load would need the sublane index provably 8-aligned,
    which a loop counter is not). r_ref [n, 4n]. `rest` is
    (h0, c0, hs, hT, cT) refs, optionally with a leading p_ref [3, n] of
    diagonal Graves peephole weights (pi, pf, po): i/f gates see c_prev,
    the o gate sees c_new (LSTMHelpers.java math), and/or a leading
    m_ref [bb, t, 1] f32 sequence mask (batch-major in BOTH layouts;
    the trailing singleton makes the per-step read a dynamic SUBLANE
    index — legal for f32 — where a [bb, t] layout would need a dynamic
    lane index, which Mosaic rejects) with the reference's masked-step
    semantics (MaskedReductionUtil role): output zeroed, h/c carries
    pass through unchanged."""
    idx = 0
    p_ref = m_ref = None
    if peephole:
        p_ref = rest[idx]
        idx += 1
    if masked:
        m_ref = rest[idx]
        idx += 1
    h0_ref, c0_ref, hs_ref, hT_ref, cT_ref = rest[idx:]
    n = r_ref.shape[0]
    r = r_ref[:].astype(jnp.float32)  # hoisted: one convert, not t
    if p_ref is not None:
        pi = p_ref[0, :].astype(jnp.float32)
        pf = p_ref[1, :].astype(jnp.float32)
        po = p_ref[2, :].astype(jnp.float32)
    else:
        pi = pf = po = jnp.float32(0.0)

    def step(i, carry):
        h, c = carry
        z_t = zx_ref[i, :, :] if time_major else zx_ref[:, i, :]
        z = z_t.astype(jnp.float32) + jnp.dot(
            h, r, preferred_element_type=jnp.float32)
        zi = jax.nn.sigmoid(z[:, 0 * n:1 * n] + pi * c)
        zf = jax.nn.sigmoid(z[:, 1 * n:2 * n] + pf * c)
        zg = jnp.tanh(z[:, 2 * n:3 * n])
        c_new = zf * c + zi * zg
        zo = jax.nn.sigmoid(z[:, 3 * n:4 * n] + po * c_new)
        h_new = zo * jnp.tanh(c_new)
        if m_ref is not None:
            live = m_ref[:, i, :] > 0  # [bb, 1]
            h_out = jnp.where(live, h_new, 0.0)
            h_new = jnp.where(live, h_new, h)
            c_new = jnp.where(live, c_new, c)
        else:
            h_out = h_new
        if time_major:
            hs_ref[i, :, :] = h_out.astype(hs_ref.dtype)
        else:
            hs_ref[:, i, :] = h_out.astype(hs_ref.dtype)
        return h_new, c_new

    h, c = lax.fori_loop(
        0, t, step,
        (h0_ref[:].astype(jnp.float32), c0_ref[:].astype(jnp.float32)))
    hT_ref[:] = h.astype(hT_ref.dtype)
    cT_ref[:] = c.astype(cT_ref.dtype)


def _lstm_fwd(zx, R, h0, c0, *, block_b: int, interpret: bool, p=None,
              mask=None):
    """Shared pallas_call wrapper for the plain and peephole cells: the
    only differences are the optional p [3, n] and mask [b, t] inputs.
    f32 runs the batch-major kernel; narrower dtypes (bf16 under the
    mixed policy) take the time-major layout (time_major flag of
    _lstm_kernel). The mask rides batch-major as [bb, t, 1] f32 in
    either layout (see _lstm_kernel on why the trailing singleton)."""
    b, t, n4 = zx.shape
    n = n4 // 4
    grid = (pl.cdiv(b, block_b),)
    time_major = zx.dtype != jnp.float32
    kernel = functools.partial(_lstm_kernel, t=t, time_major=time_major,
                               peephole=p is not None,
                               masked=mask is not None)
    if time_major:
        zx_in = jnp.swapaxes(zx, 0, 1)  # [t, b, 4n]
        zx_spec = pl.BlockSpec((t, block_b, n4), lambda i: (0, i, 0))
        hs_spec = pl.BlockSpec((t, block_b, n), lambda i: (0, i, 0))
        hs_shape = (t, b, n)
    else:
        zx_in = zx
        zx_spec = pl.BlockSpec((block_b, t, n4), lambda i: (i, 0, 0))
        hs_spec = pl.BlockSpec((block_b, t, n), lambda i: (i, 0, 0))
        hs_shape = (b, t, n)
    in_specs = [zx_spec, pl.BlockSpec((n, n4), lambda i: (0, 0))]
    args = [zx_in, R]
    if p is not None:
        in_specs.append(pl.BlockSpec((3, n), lambda i: (0, 0)))
        args.append(p)
    if mask is not None:
        in_specs.append(pl.BlockSpec((block_b, t, 1), lambda i: (i, 0, 0)))
        args.append(mask.astype(jnp.float32)[..., None])
    in_specs += [
        pl.BlockSpec((block_b, n), lambda i: (i, 0)),
        pl.BlockSpec((block_b, n), lambda i: (i, 0)),
    ]
    args += [h0, c0]
    hs, hT, cT = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct(hs_shape, zx.dtype),
            jax.ShapeDtypeStruct((b, n), zx.dtype),
            jax.ShapeDtypeStruct((b, n), zx.dtype),
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            hs_spec,
            pl.BlockSpec((block_b, n), lambda i: (i, 0)),
            pl.BlockSpec((block_b, n), lambda i: (i, 0)),
        ),
        name=kernel_name("lstm_fwd", zx.dtype, b=b, t=t, n=n, bb=block_b),
        interpret=interpret,
    )(*args)
    if time_major:
        hs = jnp.swapaxes(hs, 0, 1)
    return hs, hT, cT


def _lstm_ref(zx, R, h0, c0, p=None, mask=None):
    """XLA lax.scan reference — identical math (incl. optional peepholes
    and masked-step carry-through), used for the backward fallback and
    the equivalence tests."""
    n = R.shape[0]
    pi, pf, po = (p[0], p[1], p[2]) if p is not None else (0.0, 0.0, 0.0)

    def cell(carry, inp):
        h, c = carry
        z_t, m_t = inp
        z = z_t + h @ R
        zi = jax.nn.sigmoid(z[:, 0 * n:1 * n] + pi * c)
        zf = jax.nn.sigmoid(z[:, 1 * n:2 * n] + pf * c)
        zg = jnp.tanh(z[:, 2 * n:3 * n])
        c_new = zf * c + zi * zg
        zo = jax.nn.sigmoid(z[:, 3 * n:4 * n] + po * c_new)
        h_new = zo * jnp.tanh(c_new)
        if m_t is None:
            return (h_new, c_new), h_new
        live = m_t[:, None] > 0
        h_out = jnp.where(live, h_new, jnp.zeros_like(h_new))
        return (jnp.where(live, h_new, h),
                jnp.where(live, c_new, c)), h_out

    m_ts = None if mask is None else jnp.swapaxes(
        mask.astype(zx.dtype), 0, 1)
    (hT, cT), hs = lax.scan(cell, (h0, c0),
                            (jnp.swapaxes(zx, 0, 1), m_ts))
    return jnp.swapaxes(hs, 0, 1), hT, cT


def _lstm_peephole_ref(zx, R, p, h0, c0, mask=None):
    """Argument-order shim for the peephole vjp."""
    return _lstm_ref(zx, R, h0, c0, p, mask)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def lstm_scan_peephole(zx, R, p, h0, c0, block_b: int = 8,
                       interpret: bool = False, mask=None):
    """Fused Graves-peephole LSTM over all timesteps (the GravesLSTM /
    GravesBidirectionalLSTM hot path — LSTMHelpers.java:206-212 role).

    zx [b, t, 4n] = x @ W + bias; R [n, 4n]; p [3, n] diag peephole
    weights (pi, pf, po); h0/c0 [b, n]; mask [b, t] optional sequence
    mask (masked steps: zero output, carry-through state). Returns
    (hs, hT, cT). Backward is the fused pallas kernel (same policy as
    lstm_scan)."""
    bb = min(block_b, zx.shape[0])
    return _lstm_fwd(zx, R, h0, c0, block_b=bb, interpret=interpret, p=p,
                     mask=mask)


def _lstm_peephole_vjp_fwd(zx, R, p, h0, c0, block_b, interpret,
                           mask=None):
    out = lstm_scan_peephole(zx, R, p, h0, c0, block_b, interpret, mask)
    return out, (zx, R, p, h0, c0, out[0], mask)


def _lstm_peephole_vjp_bwd(block_b, interpret, res, g):
    zx, R, p, h0, c0, hs, mask = res
    got = _lstm_bwd(zx, R, h0, c0, hs, g, interpret=interpret, p=p,
                    mask=mask)
    if got is None:  # over the bwd VMEM budget: XLA-recompute fallback
        _, vjp = jax.vjp(
            lambda zx, R, p, h0, c0: _lstm_peephole_ref(
                zx, R, p, h0, c0, mask), zx, R, p, h0, c0)
        dmask = None if mask is None else zeros_cotangent(mask)
        return vjp(g) + (dmask,)
    dzx, dR, dp, dh0, dc0 = got
    # mask cotangent is zeros: masks are data, never trained (the scan
    # path's `where` would give the same treatment under stop_gradient)
    dmask = None if mask is None else zeros_cotangent(mask)
    return (dzx.astype(zx.dtype), dR.astype(R.dtype), dp.astype(p.dtype),
            dh0.astype(h0.dtype), dc0.astype(c0.dtype), dmask)


lstm_scan_peephole.defvjp(_lstm_peephole_vjp_fwd, _lstm_peephole_vjp_bwd)


def _lstm_bwd_kernel(zx_ref, r_ref, *rest, t: int, time_major: bool,
                     peephole: bool, masked: bool, b_total: int,
                     block_b: int):
    """Fused LSTM backward — the cudnnRNNBackwardData/Weights role
    (CudnnLSTMHelper.java:612). One batch-block program, two phases, all
    intermediates VMEM-resident:

      phase 1 (forward recompute): z_t = zx_t + h_{t-1}R, gates, c_t —
          cell states land in a [t, bb, n] f32 scratch; nothing touches
          HBM beyond the zx/hs blocks the program already owns.
      phase 2 (reverse): the dh/dc recurrence with gate activations
          recomputed per step from the scratch cell states, emitting
          dzx_t per step and accumulating dR (and dp) across the
          sequential TPU grid in f32 output blocks shared by every
          batch-block program.

    Replaces the round-2 XLA-recompute vjp, whose lax.scan saved per-step
    residuals to HBM and replayed them through a second HLO loop."""
    rest = list(rest)
    p_ref = rest.pop(0) if peephole else None
    m_ref = rest.pop(0) if masked else None
    (h0_ref, c0_ref, hs_ref, ghs_ref, ghT_ref, gcT_ref) = rest[:6]
    outs = rest[6:]
    dzx_ref, dr_ref = outs[0], outs[1]
    dp_ref = outs[2] if peephole else None
    dh0_ref, dc0_ref = outs[2 + bool(peephole)], outs[3 + bool(peephole)]
    scratch = outs[4 + bool(peephole):]
    cs_ref = scratch[0]
    hcs_ref = scratch[1] if masked else None  # masked h-carry trajectory:
    # hs holds ZEROED outputs at masked steps, so the true carry that fed
    # each step's gemm has to be reconstructed in phase 1
    n = r_ref.shape[0]
    r = r_ref[:].astype(jnp.float32)
    if p_ref is not None:
        pi = p_ref[0, :].astype(jnp.float32)
        pf = p_ref[1, :].astype(jnp.float32)
        po = p_ref[2, :].astype(jnp.float32)
    else:
        pi = pf = po = jnp.float32(0.0)

    # Row-validity mask: when b % block_b != 0, the last program's padded
    # rows hold UNDEFINED block-padding data. Per-row outputs would just
    # discard it, but dR/dp are cross-row reductions shared by all
    # programs — one NaN row would poison the whole recurrent-weight
    # gradient. jnp.where (a select) rather than multiply: 0 * NaN = NaN.
    rows = pl.program_id(0) * block_b + lax.broadcasted_iota(
        jnp.int32, (block_b, 1), 0)
    valid = rows < b_total

    def _masked(a):
        return jnp.where(valid, a.astype(jnp.float32), 0.0)

    def zx_at(i):
        z = zx_ref[i, :, :] if time_major else zx_ref[:, i, :]
        return _masked(z)

    def hs_at(i):
        h = hs_ref[i, :, :] if time_major else hs_ref[:, i, :]
        return _masked(h)

    def ghs_at(i):
        g = ghs_ref[i, :, :] if time_major else ghs_ref[:, i, :]
        return _masked(g)

    def gates(z, c_prev, c_new=None):
        """Gate activations from pre-activations + cell states."""
        zi = jax.nn.sigmoid(z[:, 0 * n:1 * n] + pi * c_prev)
        zf = jax.nn.sigmoid(z[:, 1 * n:2 * n] + pf * c_prev)
        zg = jnp.tanh(z[:, 2 * n:3 * n])
        if c_new is None:
            c_new = zf * c_prev + zi * zg
        zo = jax.nn.sigmoid(z[:, 3 * n:4 * n] + po * c_new)
        return zi, zf, zg, zo, c_new

    def m_at(i):
        return m_ref[:, i, :] > 0  # [bb, 1] bool

    # ---- phase 1: forward recompute of cell states into VMEM scratch
    # (plus the h-carry trajectory when masked — hs can't provide it)
    def fwd_step(i, carry):
        h, c = carry
        z = zx_at(i) + jnp.dot(h, r, preferred_element_type=jnp.float32)
        zi, zf, zg, zo, c_new = gates(z, c)
        if m_ref is not None:
            live = m_at(i)
            h_new = zo * jnp.tanh(c_new)
            h_next = jnp.where(live, h_new, h)
            c_next = jnp.where(live, c_new, c)
            hcs_ref[i, :, :] = h_next
        else:
            h_next = hs_at(i)
            c_next = c_new
        cs_ref[i, :, :] = c_next
        return h_next, c_next

    lax.fori_loop(0, t, fwd_step,
                  (_masked(h0_ref[:]), _masked(c0_ref[:])))

    # ---- phase 2: reverse recurrence
    first = pl.program_id(0) == 0
    rT = r.T  # hoisted transpose for the dh gemm

    def bwd_step(h_prev, c_prev, c_new, z, dh_next, dc_next, i):
        """One reverse step. Masked steps are identity in the forward
        (zero output, carried state), so their cotangents pass straight
        through: dz = 0, dH/dC forwarded unchanged."""
        if m_ref is not None:
            live = m_at(i)
            dh = jnp.where(live, ghs_at(i) + dh_next, 0.0)
            dc_in = jnp.where(live, dc_next, 0.0)
        else:
            dh = ghs_at(i) + dh_next
            dc_in = dc_next
        zi, zf, zg, zo, _ = gates(z, c_prev, c_new)
        tc = jnp.tanh(c_new)
        dzo = dh * tc * zo * (1.0 - zo)
        dc = dh * zo * (1.0 - tc * tc) + dc_in + po * dzo
        dzg = dc * zi * (1.0 - zg * zg)
        dzi = dc * zg * zi * (1.0 - zi)
        dzf = dc * c_prev * zf * (1.0 - zf)
        dz = jnp.concatenate([dzi, dzf, dzg, dzo], axis=-1)
        if time_major:
            dzx_ref[i, :, :] = dz.astype(dzx_ref.dtype)
        else:
            dzx_ref[:, i, :] = dz.astype(dzx_ref.dtype)
        dr_ref[:, :] += jnp.dot(h_prev.T, dz,
                                preferred_element_type=jnp.float32)
        if dp_ref is not None:
            dp_ref[0, :] += jnp.sum(dzi * c_prev, axis=0)
            dp_ref[1, :] += jnp.sum(dzf * c_prev, axis=0)
            dp_ref[2, :] += jnp.sum(dzo * c_new, axis=0)
        dh_prev = jnp.dot(dz, rT, preferred_element_type=jnp.float32)
        dc_prev = dc * zf + pi * dzi + pf * dzf
        if m_ref is not None:
            dh_prev = dh_prev + jnp.where(live, 0.0, dh_next)
            dc_prev = dc_prev + jnp.where(live, 0.0, dc_next)
        return dh_prev, dc_prev

    # the shared dR/dp blocks are revisited by every batch-block program:
    # zero them once, in the first program
    @pl.when(first)
    def _():
        dr_ref[:, :] = jnp.zeros_like(dr_ref)
        if dp_ref is not None:
            dp_ref[:, :] = jnp.zeros_like(dp_ref)

    def h_carry_at(i):
        # the carry that fed step i+1's gemm: with a mask, hs holds the
        # ZEROED outputs, so the true trajectory comes from scratch
        if m_ref is not None:
            return hcs_ref[i, :, :]
        return hs_at(i)

    def rev_step(j, carry):
        dh_next, dc_next = carry
        i = t - 1 - j  # t-1 .. 1 (step 0 handled after the loop)
        h_prev = h_carry_at(i - 1)
        c_prev = cs_ref[i - 1, :, :]
        c_new = cs_ref[i, :, :]
        z = zx_at(i) + jnp.dot(h_prev, r,
                               preferred_element_type=jnp.float32)
        return bwd_step(h_prev, c_prev, c_new, z, dh_next, dc_next, i)

    dh0 = _masked(ghT_ref[:])
    dc0 = _masked(gcT_ref[:])
    if t > 1:
        dh0, dc0 = lax.fori_loop(0, t - 1, rev_step, (dh0, dc0))
    # step 0 reads the true initial carries
    h_prev = _masked(h0_ref[:])
    c_prev = _masked(c0_ref[:])
    z = zx_at(0) + jnp.dot(h_prev, r, preferred_element_type=jnp.float32)
    dh0, dc0 = bwd_step(h_prev, c_prev, cs_ref[0, :, :], z, dh0, dc0, 0)
    dh0_ref[:] = dh0.astype(dh0_ref.dtype)
    dc0_ref[:] = dc0.astype(dc0_ref.dtype)


def pick_lstm_bwd_block(shape, dtype, masked: bool = False) -> int:
    """Batch block for the backward kernel. Its VMEM residency per row is
    larger than the forward's: zx + dzx (4n each) + hs + g_hs (n each) in
    the block dtype, plus the [t, bb, n] f32 cell-state scratch (doubled
    when masked: the h-carry trajectory needs its own scratch) — so the
    budget divides by ~2.7x more bytes/row than the forward picker.
    Same 8-alignment and 0-means-fall-back contract as pick_lstm_block."""
    b, t, n4 = shape
    n = n4 // 4
    itemsize = jnp.dtype(dtype).itemsize
    row_bytes = t * ((n4 + n4 + n + n) * itemsize
                     + n * 4 * (2 if masked else 1))
    bb = (6 << 20) // max(row_bytes, 1)
    bb = min(bb, b)
    bb -= bb % 8
    return int(bb) if bb >= 8 else 0


def _lstm_bwd(zx, R, h0, c0, hs, g, *, interpret: bool, p=None,
              mask=None):
    """pallas_call wrapper for the fused backward; returns
    (dzx, dR[f32], dp[f32]|None, dh0, dc0) or None when the block does
    not fit (callers then use the XLA-recompute vjp)."""
    b, t, n4 = zx.shape
    n = n4 // 4
    bb = pick_lstm_bwd_block(zx.shape, zx.dtype, masked=mask is not None)
    if bb == 0:
        return None
    g_hs, g_hT, g_cT = g
    time_major = zx.dtype != jnp.float32
    kernel = functools.partial(_lstm_bwd_kernel, t=t,
                               time_major=time_major,
                               peephole=p is not None,
                               masked=mask is not None,
                               b_total=b, block_b=bb)
    grid = (pl.cdiv(b, bb),)

    def seq_spec():
        if time_major:
            return pl.BlockSpec((t, bb, n), lambda i: (0, i, 0))
        return pl.BlockSpec((bb, t, n), lambda i: (i, 0, 0))

    def seq4_spec():
        if time_major:
            return pl.BlockSpec((t, bb, n4), lambda i: (0, i, 0))
        return pl.BlockSpec((bb, t, n4), lambda i: (i, 0, 0))

    def tm(a):
        return jnp.swapaxes(a, 0, 1) if time_major else a

    carry_spec = pl.BlockSpec((bb, n), lambda i: (i, 0))
    in_specs = [seq4_spec(), pl.BlockSpec((n, n4), lambda i: (0, 0))]
    args = [tm(zx), R]
    if p is not None:
        in_specs.append(pl.BlockSpec((3, n), lambda i: (0, 0)))
        args.append(p)
    if mask is not None:
        in_specs.append(pl.BlockSpec((bb, t, 1), lambda i: (i, 0, 0)))
        args.append(mask.astype(jnp.float32)[..., None])
    in_specs += [carry_spec, carry_spec, seq_spec(), seq_spec(),
                 carry_spec, carry_spec]
    args += [h0, c0, tm(hs), tm(g_hs), g_hT, g_cT]

    dzx_shape = (t, b, n4) if time_major else (b, t, n4)
    out_shape = [
        jax.ShapeDtypeStruct(dzx_shape, zx.dtype),
        jax.ShapeDtypeStruct((n, n4), jnp.float32),
    ]
    out_specs = [seq4_spec(), pl.BlockSpec((n, n4), lambda i: (0, 0))]
    if p is not None:
        out_shape.append(jax.ShapeDtypeStruct((3, n), jnp.float32))
        out_specs.append(pl.BlockSpec((3, n), lambda i: (0, 0)))
    out_shape += [jax.ShapeDtypeStruct((b, n), jnp.float32),
                  jax.ShapeDtypeStruct((b, n), jnp.float32)]
    out_specs += [carry_spec, carry_spec]

    scratch = [pltpu.VMEM((t, bb, n), jnp.float32)]
    if mask is not None:
        scratch.append(pltpu.VMEM((t, bb, n), jnp.float32))
    outs = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shape),
        grid=grid,
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        scratch_shapes=scratch,
        name=kernel_name("lstm_bwd", zx.dtype, b=b, t=t, n=n, bb=bb),
        interpret=interpret,
    )(*args)
    if p is not None:
        dzx, dR, dp, dh0, dc0 = outs
    else:
        dzx, dR, dh0, dc0 = outs
        dp = None
    if time_major:
        dzx = jnp.swapaxes(dzx, 0, 1)
    return dzx, dR, dp, dh0, dc0


def pick_lstm_block(shape, dtype) -> int:
    """Batch block for the LSTM kernels, owned here with the kernel's
    memory model: the grid program holds a [bb, t, 4n] zx block plus a
    [bb, t, n] hs block (and R/carries) in VMEM, so bb is sized to keep
    zx+hs within ~6MB (gradient recompute and Mosaic's own staging need
    the rest of the ~16MB VMEM; a 10MB zx+hs block measured as a compile
    failure), rounded DOWN to a multiple of 8
    (the bf16 time-major layout tiles bb into sublanes, whose block
    offsets must be 8-aligned). Returns 0 when even an 8-row block cannot
    fit — callers must then use their lax.scan path. Larger blocks
    amortize the recurrent weights over more rows (16 measured ~2.3x
    faster than 8 at the char-RNN bench shape; 32 fails the VMEM fit
    there once gradients are involved)."""
    b, t, n4 = shape
    itemsize = jnp.dtype(dtype).itemsize
    row_bytes = t * (n4 + n4 // 4) * itemsize  # zx row + hs row
    bb = (6 << 20) // max(row_bytes, 1)
    bb = min(bb, b)
    bb -= bb % 8
    return int(bb) if bb >= 8 else 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def lstm_scan(zx, R, h0, c0, block_b: int = 8, interpret: bool = False,
              mask=None):
    """Fused LSTM over all timesteps.

    zx [b, t, 4n] = x @ W + bias (hoisted big gemm, done by the caller on
    the MXU); R [n, 4n] recurrent weights; h0/c0 [b, n]; mask [b, t]
    optional sequence mask (masked steps: zero output, carry-through
    state — MaskedReductionUtil semantics). Returns (hs [b, t, n], hT,
    cT). Gate order i,f,g,o (Keras layout, same as
    nn/layers/recurrent.py)."""
    bb = min(block_b, zx.shape[0])
    return _lstm_fwd(zx, R, h0, c0, block_b=bb, interpret=interpret,
                     mask=mask)


def _lstm_vjp_fwd(zx, R, h0, c0, block_b, interpret, mask=None):
    out = lstm_scan(zx, R, h0, c0, block_b, interpret, mask)
    return out, (zx, R, h0, c0, out[0], mask)


def _lstm_vjp_bwd(block_b, interpret, res, g):
    zx, R, h0, c0, hs, mask = res
    got = _lstm_bwd(zx, R, h0, c0, hs, g, interpret=interpret, mask=mask)
    if got is None:  # over the bwd VMEM budget: XLA-recompute fallback
        _, vjp = jax.vjp(
            lambda zx, R, h0, c0: _lstm_ref(zx, R, h0, c0, None, mask),
            zx, R, h0, c0)
        dmask = None if mask is None else zeros_cotangent(mask)
        return vjp(g) + (dmask,)
    dzx, dR, _, dh0, dc0 = got
    dmask = None if mask is None else zeros_cotangent(mask)
    return (dzx.astype(zx.dtype), dR.astype(R.dtype),
            dh0.astype(h0.dtype), dc0.astype(c0.dtype), dmask)


lstm_scan.defvjp(_lstm_vjp_fwd, _lstm_vjp_bwd)


# ---------------------------------------------------------------------------
# time-chunked LSTM kernels — the long-sequence regime
# ---------------------------------------------------------------------------
# The kernels above keep the full [bb, t, 4n] slab VMEM-resident, so at
# t=1024/n=256 even one 8-row block exceeds the budget. These variants
# shed exactly that residency: the grid gains a TIME dimension, zx/hs
# stream through VMEM one [bb, tc, 4n] chunk at a time, and the (h, c)
# recurrence carries across chunks in VMEM scratch (the xent kernel's
# running-accumulator pattern). The forward additionally checkpoints the
# carry state at every chunk boundary ([nt, b, n] — KBs, not MBs), which
# is what lets the backward revisit chunks in REVERSE grid order and
# recompute each chunk's cell states locally (chunked-BPTT recompute, the
# cudnnRNNBackwardData role at sequence lengths cuDNN handles with its
# own internal streaming).


def _lstm_chunk_fwd_kernel(zx_ref, r_ref, *rest, tc: int, nt: int,
                           time_major: bool, peephole: bool, masked: bool):
    """One (batch-block, time-chunk) program; h/c ride VMEM scratch
    across the sequential time grid."""
    idx = 0
    p_ref = m_ref = None
    if peephole:
        p_ref = rest[idx]
        idx += 1
    if masked:
        m_ref = rest[idx]
        idx += 1
    (h0_ref, c0_ref, hs_ref, hT_ref, cT_ref, hck_ref, cck_ref,
     h_sc, c_sc) = rest[idx:]
    j = pl.program_id(1)
    n = r_ref.shape[0]
    r = r_ref[:].astype(jnp.float32)
    if p_ref is not None:
        pi = p_ref[0, :].astype(jnp.float32)
        pf = p_ref[1, :].astype(jnp.float32)
        po = p_ref[2, :].astype(jnp.float32)
    else:
        pi = pf = po = jnp.float32(0.0)

    @pl.when(j == 0)
    def _():
        h_sc[:] = h0_ref[:].astype(jnp.float32)
        c_sc[:] = c0_ref[:].astype(jnp.float32)

    # checkpoint the carry ENTERING this chunk (ckpt[0] == h0/c0)
    hck_ref[0, :, :] = h_sc[:]
    cck_ref[0, :, :] = c_sc[:]

    def step(i, carry):
        h, c = carry
        z_t = zx_ref[i, :, :] if time_major else zx_ref[:, i, :]
        z = z_t.astype(jnp.float32) + jnp.dot(
            h, r, preferred_element_type=jnp.float32)
        zi = jax.nn.sigmoid(z[:, 0 * n:1 * n] + pi * c)
        zf = jax.nn.sigmoid(z[:, 1 * n:2 * n] + pf * c)
        zg = jnp.tanh(z[:, 2 * n:3 * n])
        c_new = zf * c + zi * zg
        zo = jax.nn.sigmoid(z[:, 3 * n:4 * n] + po * c_new)
        h_new = zo * jnp.tanh(c_new)
        if m_ref is not None:
            live = m_ref[:, i, :] > 0
            h_out = jnp.where(live, h_new, 0.0)
            h_new = jnp.where(live, h_new, h)
            c_new = jnp.where(live, c_new, c)
        else:
            h_out = h_new
        if time_major:
            hs_ref[i, :, :] = h_out.astype(hs_ref.dtype)
        else:
            hs_ref[:, i, :] = h_out.astype(hs_ref.dtype)
        return h_new, c_new

    h, c = lax.fori_loop(0, tc, step, (h_sc[:], c_sc[:]))
    h_sc[:] = h
    c_sc[:] = c

    @pl.when(j == nt - 1)
    def _():
        hT_ref[:] = h.astype(hT_ref.dtype)
        cT_ref[:] = c.astype(cT_ref.dtype)


def _lstm_chunk_bwd_kernel(zx_ref, r_ref, *rest, tc: int, nt: int,
                           time_major: bool, peephole: bool, masked: bool,
                           b_total: int, block_b: int):
    """Reverse sweep over time chunks (grid index maps run j -> chunk
    nt-1-j): phase 1 recomputes THIS chunk's cell states from the
    forward's boundary checkpoints, phase 2 runs the dh/dc recurrence,
    carried across chunks in scratch."""
    rest = list(rest)
    p_ref = rest.pop(0) if peephole else None
    m_ref = rest.pop(0) if masked else None
    (hck_ref, cck_ref, ghs_ref, ghT_ref, gcT_ref) = rest[:5]
    outs = rest[5:]
    dzx_ref, dr_ref = outs[0], outs[1]
    dp_ref = outs[2] if peephole else None
    dh0_ref, dc0_ref = outs[2 + bool(peephole)], outs[3 + bool(peephole)]
    scratch = outs[4 + bool(peephole):]
    cs_ref = scratch[0]
    hcs_ref = scratch[1]  # within-chunk h-carry trajectory (always kept:
    # unlike the full-t kernel there is no hs block to read it from —
    # hcs[i] = carry entering step i+1; hcs[0] holds the chunk-entry h)
    dh_sc, dc_sc = scratch[-2], scratch[-1]
    j = pl.program_id(1)
    n = r_ref.shape[0]
    r = r_ref[:].astype(jnp.float32)
    if p_ref is not None:
        pi = p_ref[0, :].astype(jnp.float32)
        pf = p_ref[1, :].astype(jnp.float32)
        po = p_ref[2, :].astype(jnp.float32)
    else:
        pi = pf = po = jnp.float32(0.0)

    rows = pl.program_id(0) * block_b + lax.broadcasted_iota(
        jnp.int32, (block_b, 1), 0)
    valid = rows < b_total

    def _masked(a):
        return jnp.where(valid, a.astype(jnp.float32), 0.0)

    def zx_at(i):
        z = zx_ref[i, :, :] if time_major else zx_ref[:, i, :]
        return _masked(z)

    def ghs_at(i):
        g = ghs_ref[i, :, :] if time_major else ghs_ref[:, i, :]
        return _masked(g)

    def gates(z, c_prev, c_new=None):
        zi = jax.nn.sigmoid(z[:, 0 * n:1 * n] + pi * c_prev)
        zf = jax.nn.sigmoid(z[:, 1 * n:2 * n] + pf * c_prev)
        zg = jnp.tanh(z[:, 2 * n:3 * n])
        if c_new is None:
            c_new = zf * c_prev + zi * zg
        zo = jax.nn.sigmoid(z[:, 3 * n:4 * n] + po * c_new)
        return zi, zf, zg, zo, c_new

    def m_at(i):
        return m_ref[:, i, :] > 0

    # ---- phase 1: recompute this chunk's cell states from the
    # checkpointed chunk-entry carries
    def fwd_step(i, carry):
        h, c = carry
        hcs_ref[i, :, :] = h
        z = zx_at(i) + jnp.dot(h, r, preferred_element_type=jnp.float32)
        zi, zf, zg, zo, c_new = gates(z, c)
        h_new = zo * jnp.tanh(c_new)
        if m_ref is not None:
            live = m_at(i)
            h_new = jnp.where(live, h_new, h)
            c_new = jnp.where(live, c_new, c)
        cs_ref[i, :, :] = c_new
        return h_new, c_new

    lax.fori_loop(0, tc, fwd_step,
                  (_masked(hck_ref[0, :, :]), _masked(cck_ref[0, :, :])))

    first = (pl.program_id(0) == 0) & (j == 0)

    @pl.when(first)
    def _():
        dr_ref[:, :] = jnp.zeros_like(dr_ref)
        if dp_ref is not None:
            dp_ref[:, :] = jnp.zeros_like(dp_ref)

    @pl.when(j == 0)  # chunk nt-1: seed from the terminal cotangents
    def _():
        dh_sc[:] = _masked(ghT_ref[:])
        dc_sc[:] = _masked(gcT_ref[:])

    rT = r.T

    def bwd_step(h_prev, c_prev, c_new, z, dh_next, dc_next, i):
        if m_ref is not None:
            live = m_at(i)
            dh = jnp.where(live, ghs_at(i) + dh_next, 0.0)
            dc_in = jnp.where(live, dc_next, 0.0)
        else:
            dh = ghs_at(i) + dh_next
            dc_in = dc_next
        zi, zf, zg, zo, _ = gates(z, c_prev, c_new)
        tcs = jnp.tanh(c_new)
        dzo = dh * tcs * zo * (1.0 - zo)
        dc = dh * zo * (1.0 - tcs * tcs) + dc_in + po * dzo
        dzg = dc * zi * (1.0 - zg * zg)
        dzi = dc * zg * zi * (1.0 - zi)
        dzf = dc * c_prev * zf * (1.0 - zf)
        dz = jnp.concatenate([dzi, dzf, dzg, dzo], axis=-1)
        if time_major:
            dzx_ref[i, :, :] = dz.astype(dzx_ref.dtype)
        else:
            dzx_ref[:, i, :] = dz.astype(dzx_ref.dtype)
        dr_ref[:, :] += jnp.dot(h_prev.T, dz,
                                preferred_element_type=jnp.float32)
        if dp_ref is not None:
            dp_ref[0, :] += jnp.sum(dzi * c_prev, axis=0)
            dp_ref[1, :] += jnp.sum(dzf * c_prev, axis=0)
            dp_ref[2, :] += jnp.sum(dzo * c_new, axis=0)
        dh_prev = jnp.dot(dz, rT, preferred_element_type=jnp.float32)
        dc_prev = dc * zf + pi * dzi + pf * dzf
        if m_ref is not None:
            dh_prev = dh_prev + jnp.where(live, 0.0, dh_next)
            dc_prev = dc_prev + jnp.where(live, 0.0, dc_next)
        return dh_prev, dc_prev

    def rev_step(k, carry):
        dh_next, dc_next = carry
        i = tc - 1 - k
        h_prev = hcs_ref[i, :, :]
        c_prev = jnp.where(i > 0, cs_ref[jnp.maximum(i - 1, 0), :, :],
                           _masked(cck_ref[0, :, :]))
        c_new = cs_ref[i, :, :]
        z = zx_at(i) + jnp.dot(h_prev, r,
                               preferred_element_type=jnp.float32)
        return bwd_step(h_prev, c_prev, c_new, z, dh_next, dc_next, i)

    dh, dc = lax.fori_loop(0, tc, rev_step, (dh_sc[:], dc_sc[:]))
    dh_sc[:] = dh
    dc_sc[:] = dc

    @pl.when(j == nt - 1)  # chunk 0: the initial-carry cotangents
    def _():
        dh0_ref[:] = dh.astype(dh0_ref.dtype)
        dc0_ref[:] = dc.astype(dc0_ref.dtype)


def pick_lstm_chunk(shape, dtype, masked: bool = False):
    """(block_b, tc) for the time-chunked kernels, or None. The backward
    is the binding program: zx + dzx chunks (4n each) + ghs chunk (n) in
    the block dtype, plus f32 cell-state and h-carry scratch (2n). tc
    must divide t (checkpoint grid); prefer LARGE chunks (fewer grid
    steps) with the whole batch in one block when it fits."""
    b, t, n4 = shape
    n = n4 // 4
    itemsize = jnp.dtype(dtype).itemsize
    for bb in (b if b % 8 == 0 else 0, 64, 32, 16, 8):
        if not bb or bb > b or b % bb:
            continue
        step_bytes = bb * ((2 * n4 + n) * itemsize + 2 * n * 4
                           + (4 if masked else 0))
        for tck in (512, 256, 128, 64, 32, 16, 8):
            if t % tck:
                continue
            if tck * step_bytes <= (6 << 20):
                return int(bb), int(tck)
    return None


def _lstm_chunked(zx, R, h0, c0, bb, tck, interpret, p=None, mask=None):
    b, t, n4 = zx.shape
    n = n4 // 4
    nt = t // tck
    time_major = zx.dtype != jnp.float32
    kernel = functools.partial(_lstm_chunk_fwd_kernel, tc=tck, nt=nt,
                               time_major=time_major,
                               peephole=p is not None,
                               masked=mask is not None)
    grid = (pl.cdiv(b, bb), nt)
    if time_major:
        zx_in = jnp.swapaxes(zx, 0, 1)
        zx_spec = pl.BlockSpec((tck, bb, n4), lambda i, j: (j, i, 0))
        hs_spec = pl.BlockSpec((tck, bb, n), lambda i, j: (j, i, 0))
        hs_shape = (t, b, n)
    else:
        zx_in = zx
        zx_spec = pl.BlockSpec((bb, tck, n4), lambda i, j: (i, j, 0))
        hs_spec = pl.BlockSpec((bb, tck, n), lambda i, j: (i, j, 0))
        hs_shape = (b, t, n)
    carry = pl.BlockSpec((bb, n), lambda i, j: (i, 0))
    ck_spec = pl.BlockSpec((1, bb, n), lambda i, j: (j, i, 0))
    in_specs = [zx_spec, pl.BlockSpec((n, n4), lambda i, j: (0, 0))]
    args = [zx_in, R]
    if p is not None:
        in_specs.append(pl.BlockSpec((3, n), lambda i, j: (0, 0)))
        args.append(p)
    if mask is not None:
        in_specs.append(pl.BlockSpec((bb, tck, 1), lambda i, j: (i, j, 0)))
        args.append(mask.astype(jnp.float32)[..., None])
    in_specs += [carry, carry]
    args += [h0, c0]
    hs, hT, cT, hck, cck = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct(hs_shape, zx.dtype),
            jax.ShapeDtypeStruct((b, n), zx.dtype),
            jax.ShapeDtypeStruct((b, n), zx.dtype),
            jax.ShapeDtypeStruct((nt, b, n), jnp.float32),
            jax.ShapeDtypeStruct((nt, b, n), jnp.float32),
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=(hs_spec, carry, carry, ck_spec, ck_spec),
        scratch_shapes=[pltpu.VMEM((bb, n), jnp.float32),
                        pltpu.VMEM((bb, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=kernel_name("lstm_chunk_fwd", zx.dtype, b=b, t=t, n=n, bb=bb,
                    tc=tck),
        interpret=interpret,
    )(*args)
    if time_major:
        hs = jnp.swapaxes(hs, 0, 1)
    return hs, hT, cT, hck, cck


def _lstm_chunked_bwd(zx, R, hck, cck, g, bb, tck, interpret, p=None,
                      mask=None):
    b, t, n4 = zx.shape
    n = n4 // 4
    nt = t // tck
    g_hs, g_hT, g_cT = g
    time_major = zx.dtype != jnp.float32
    kernel = functools.partial(_lstm_chunk_bwd_kernel, tc=tck, nt=nt,
                               time_major=time_major,
                               peephole=p is not None,
                               masked=mask is not None,
                               b_total=b, block_b=bb)
    grid = (pl.cdiv(b, bb), nt)
    rj = lambda j: nt - 1 - j  # reverse chunk order

    if time_major:
        seq4 = pl.BlockSpec((tck, bb, n4), lambda i, j: (rj(j), i, 0))
        seq = pl.BlockSpec((tck, bb, n), lambda i, j: (rj(j), i, 0))
    else:
        seq4 = pl.BlockSpec((bb, tck, n4), lambda i, j: (i, rj(j), 0))
        seq = pl.BlockSpec((bb, tck, n), lambda i, j: (i, rj(j), 0))
    carry = pl.BlockSpec((bb, n), lambda i, j: (i, 0))
    ck_spec = pl.BlockSpec((1, bb, n), lambda i, j: (rj(j), i, 0))

    def tm(a):
        return jnp.swapaxes(a, 0, 1) if time_major else a

    in_specs = [seq4, pl.BlockSpec((n, n4), lambda i, j: (0, 0))]
    args = [tm(zx), R]
    if p is not None:
        in_specs.append(pl.BlockSpec((3, n), lambda i, j: (0, 0)))
        args.append(p)
    if mask is not None:
        in_specs.append(
            pl.BlockSpec((bb, tck, 1), lambda i, j: (i, rj(j), 0)))
        args.append(mask.astype(jnp.float32)[..., None])
    in_specs += [ck_spec, ck_spec, seq, carry, carry]
    args += [hck, cck, tm(g_hs), g_hT, g_cT]

    dzx_shape = (t, b, n4) if time_major else (b, t, n4)
    out_shape = [jax.ShapeDtypeStruct(dzx_shape, zx.dtype),
                 jax.ShapeDtypeStruct((n, n4), jnp.float32)]
    out_specs = [seq4, pl.BlockSpec((n, n4), lambda i, j: (0, 0))]
    if p is not None:
        out_shape.append(jax.ShapeDtypeStruct((3, n), jnp.float32))
        out_specs.append(pl.BlockSpec((3, n), lambda i, j: (0, 0)))
    out_shape += [jax.ShapeDtypeStruct((b, n), jnp.float32),
                  jax.ShapeDtypeStruct((b, n), jnp.float32)]
    out_specs += [carry, carry]

    scratch = [pltpu.VMEM((tck, bb, n), jnp.float32),
               pltpu.VMEM((tck, bb, n), jnp.float32),
               pltpu.VMEM((bb, n), jnp.float32),
               pltpu.VMEM((bb, n), jnp.float32)]
    outs = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shape),
        grid=grid,
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=kernel_name("lstm_chunk_bwd", zx.dtype, b=b, t=t, n=n, bb=bb,
                    tc=tck),
        interpret=interpret,
    )(*args)
    if p is not None:
        dzx, dR, dp, dh0, dc0 = outs
    else:
        dzx, dR, dh0, dc0 = outs
        dp = None
    if time_major:
        dzx = jnp.swapaxes(dzx, 0, 1)
    return dzx, dR, dp, dh0, dc0


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def lstm_scan_chunked(zx, R, h0, c0, block_b: int, tc: int,
                      interpret: bool = False, mask=None):
    """Time-chunked fused LSTM (long-sequence regime): same contract as
    lstm_scan, but zx/hs stream through VMEM chunk by chunk so t is
    unbounded by residency. Admission via pick_lstm_chunk."""
    hs, hT, cT, _, _ = _lstm_chunked(zx, R, h0, c0, block_b, tc,
                                     interpret, mask=mask)
    return hs, hT, cT


def _lstm_chunked_vjp_fwd(zx, R, h0, c0, block_b, tc, interpret,
                          mask=None):
    hs, hT, cT, hck, cck = _lstm_chunked(zx, R, h0, c0, block_b, tc,
                                         interpret, mask=mask)
    return (hs, hT, cT), (zx, R, h0, c0, hck, cck, mask)


def _lstm_chunked_vjp_bwd(block_b, tc, interpret, res, g):
    zx, R, h0, c0, hck, cck, mask = res
    dzx, dR, _, dh0, dc0 = _lstm_chunked_bwd(
        zx, R, hck, cck, g, block_b, tc, interpret, mask=mask)
    dmask = None if mask is None else zeros_cotangent(mask)
    return (dzx.astype(zx.dtype), dR.astype(R.dtype),
            dh0.astype(h0.dtype), dc0.astype(c0.dtype), dmask)


lstm_scan_chunked.defvjp(_lstm_chunked_vjp_fwd, _lstm_chunked_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def lstm_scan_chunked_peephole(zx, R, p, h0, c0, block_b: int, tc: int,
                               interpret: bool = False, mask=None):
    """Chunked variant with Graves peepholes (p [3, n] = pi, pf, po)."""
    hs, hT, cT, _, _ = _lstm_chunked(zx, R, h0, c0, block_b, tc,
                                     interpret, p=p, mask=mask)
    return hs, hT, cT


def _lstm_chunked_ph_vjp_fwd(zx, R, p, h0, c0, block_b, tc, interpret,
                             mask=None):
    hs, hT, cT, hck, cck = _lstm_chunked(zx, R, h0, c0, block_b, tc,
                                         interpret, p=p, mask=mask)
    return (hs, hT, cT), (zx, R, p, h0, c0, hck, cck, mask)


def _lstm_chunked_ph_vjp_bwd(block_b, tc, interpret, res, g):
    zx, R, p, h0, c0, hck, cck, mask = res
    dzx, dR, dp, dh0, dc0 = _lstm_chunked_bwd(
        zx, R, hck, cck, g, block_b, tc, interpret, p=p, mask=mask)
    dmask = None if mask is None else zeros_cotangent(mask)
    return (dzx.astype(zx.dtype), dR.astype(R.dtype), dp.astype(p.dtype),
            dh0.astype(h0.dtype), dc0.astype(c0.dtype), dmask)


lstm_scan_chunked_peephole.defvjp(_lstm_chunked_ph_vjp_fwd,
                                  _lstm_chunked_ph_vjp_bwd)


def chunked_lstm_auto_regime(batch: int, timesteps: int, n_hidden: int,
                             dtype) -> bool:
    """Where AUTO admits the time-chunked LSTM kernels: long float32
    sequences of a small batch of wide cells — the neighbourhood of
    b=8, n=256, t=1024 and 4096, where a builder's A/B from before the
    benchmark (no driver number) had them ahead of XLA's scan; bf16 and
    every other shape need the DL4J_TPU_PALLAS_LSTM=1 opt-in."""
    return (dtype == jnp.float32 and timesteps >= 1024
            and batch <= 16 and n_hidden >= 128)


def fused_lstm(zx, R, h0, c0, peep=None, mask=None, reverse: bool = False):
    """The sigmoid/tanh LSTM recurrence over the projected inputs
    zx [b, t, 4n] through a fused kernel: (hs [b, t, n], (hT, cT)), or
    None when none is admitted here and the caller keeps its lax.scan.
    `peep` = (pi, pf, po) for Graves peepholes, `mask` [b, t] (masked
    steps: zero output, carry-through state — in-kernel); a reverse scan
    is the same recurrence on the time-flipped input and mask.

    Two families (`lstm_helper_mode`): the full-t resident kernels only
    when forced and `pick_lstm_block` fits; the time-chunked ones when
    forced, or on their own in `chunked_lstm_auto_regime`, wherever
    `pick_lstm_chunk` fits. Under a data mesh each device scans its own
    rows, so regime and plans are judged on the per-device batch; a mesh
    that shards anything else declines."""
    if zx.dtype not in (jnp.float32, jnp.bfloat16):
        return None
    b_dev = kernel_call.per_device_batch(zx.shape[0])
    mode = lstm_helper_mode()
    forced = helpers_enabled() and mode == "forced"
    auto = (helpers_enabled() and mode != "off"
            and chunked_lstm_auto_regime(b_dev, zx.shape[1], R.shape[0],
                                         zx.dtype))
    if not (b_dev and (forced or auto)):
        return None
    masked = mask is not None
    shape_dev = (b_dev,) + tuple(zx.shape[1:])
    # the kernels own their memory models: full-t when opted in and it
    # fits, else the chunked plan
    bb = pick_lstm_block(shape_dev, zx.dtype) if forced else 0
    plan = pick_lstm_chunk(shape_dev, zx.dtype, masked=masked)
    if not (bb or plan):
        return None
    interp = kernel_call.interpret()
    if reverse:
        zx = jnp.flip(zx, axis=1)
        mask = jnp.flip(mask, axis=1) if masked else None
    # R joins the compute dtype: under the mixed policy params are f32
    # while activations are bf16, and the custom-vjp's scan reference
    # needs one consistent carry dtype
    R = R.astype(zx.dtype)
    if peep is not None:
        peep = jnp.stack(peep).astype(zx.dtype)

    def scan_kernel(zx_, h0_, c0_, m_, R_, p_):
        if bb and p_ is not None:
            return lstm_scan_peephole(zx_, R_, p_, h0_, c0_, bb, interp, m_)
        if bb:
            return lstm_scan(zx_, R_, h0_, c0_, bb, interp, m_)
        cb, tc = plan
        if p_ is not None:
            return lstm_scan_chunked_peephole(zx_, R_, p_, h0_, c0_, cb, tc,
                                              interp, m_)
        return lstm_scan_chunked(zx_, R_, h0_, c0_, cb, tc, interp, m_)

    hs, hT, cT = kernel_call.per_batch_shard(
        scan_kernel, (zx, h0, c0, mask, R, peep),
        (True, True, True, True, False, False))
    if reverse:
        hs = jnp.flip(hs, axis=1)
    return hs, (hT, cT)


def pick_flash_blocks(t: int, d: int, dtype=None) -> Tuple[int, int]:
    """(bq, bk) for flash_attention: tile selection per shape class (the
    cudnnGetConvolutionForwardAlgorithm role), and with it how a head is
    cut into programs (`_whole_head`). Measured on one TPU v5e with the
    kernels timed alone (PERF.md section 6, PR 30, has the table): one
    whole-sequence block at t <= 512; square blocks of 256 up to t 2048,
    where a head is one program whatever its width — head 64, t 1024:
    0.70 ms a forward + backward call against 0.73 at 512 and 0.84 at
    128; t 2048: 1.11 against 1.14 at 512; blocks of 512 above, where a
    block is a program — head 256, t 8192: 26.5 ms against 28.3 at
    (256, 512) and 30.1 at 256. Square, so that forward and backward mask
    the same blocks; `d` and `dtype` do not move the choice today (head
    64 and 256, bf16 and float32 were timed), and neither does a window:
    36 heads of 128 at t 8192 under a window of 512 take 5.27 ms forward +
    backward at (512, 512), where a q block visits 2 key blocks for a band
    of 1, against 6.22 at 256 (3 for 2), 10.56 at 128 (5 for 4), 5.89 at
    (256, 512), 5.97 at (512, 256) — a block's fixed cost outweighs the
    scores it saves — and 16.09 as a masked whole triangle (PERF.md
    section 6, PR 47). The
    returned blocks always divide t (or t fits in one block): a block
    that doesn't divide t would make the kernel grid silently drop rows,
    so unaligned lengths above one block raise instead."""
    if t <= 128:
        return t, t  # one block; flash_attention clamps to t
    if t % 128 != 0:
        raise ValueError(
            f"flash blocks need t % 128 == 0 (or t <= 128), got t={t}; "
            f"pad the sequence (ops.attention.choose_impl gates on this)")
    if t <= 512:
        return t, t
    blk = next(c for c in ((256, 128) if t <= 2048 else (512, 256, 128))
               if t % c == 0)
    return blk, blk


# ====================================================== conv-bn-relu epilogue
#
# The ResNet hot block is Conv2D(identity, no bias) -> BatchNorm(relu)
# (zoo ResNet50.conv_bn). The conv itself is MXU work XLA owns; the
# BatchNorm normalize + gamma/beta affine + relu tail is pure HBM-bound
# elementwise traffic — the roofline profiler classifies those steps
# memory-bound, which is the admission ticket for fusing them into ONE
# pallas pass (read x once, write y once) instead of trusting XLA's
# fusion heuristics across the conv/BN op boundary.
#
# Scope: the EPILOGUE y = act(x * scale + shift) with per-channel f32
# scale/shift (inv-stddev and -mean*inv folded with gamma/beta by the
# caller, nn/layers/normalization.py). The batch statistics stay on
# XLA's stable two-reduce path — a one-pass sum/sumsq kernel would
# reintroduce the E[x^2]-E[x]^2 cancellation that path exists to avoid.
# Backward recomputes through the reference epilogue under jax.vjp
# (exact gradients, nothing extra saved — the same recompute posture as
# the chunked LSTM backward).
#
# Admission is OPT-IN via DL4J_TPU_PALLAS_CONVBN (`fused_affine_act`): auto
# stays off until a win is measured — the lstm_helper_mode precedent.


def convbn_mode() -> str:
    """Tri-state DL4J_TPU_PALLAS_CONVBN: 'forced' (truthy — fused
    epilogue admitted wherever a block plan fits), 'off' (set falsy),
    'auto' (unset — XLA path until the A/B evidence admits a regime)."""
    return envflags.mode("DL4J_TPU_PALLAS_CONVBN")


def pick_bn_block(shape, dtype) -> int:
    """Rows per grid step for the epilogue over x reshaped [rows, c]
    (rows = every leading axis collapsed, c = channels last). 0 = no
    plan fits: rows must divide by the block and a block must stay
    within a conservative VMEM budget (~4 MB in + out resident)."""
    c = int(shape[-1])
    rows = 1
    for s in shape[:-1]:
        rows *= int(s)
    if c % 8 != 0 or rows <= 0:
        return 0
    itemsize = jnp.dtype(dtype).itemsize
    for br in (1024, 512, 256, 128, 64, 32, 16, 8):
        if rows % br == 0 and 2 * br * c * itemsize <= 4 * 2 ** 20:
            return br
    return 0


def _bn_act_kernel(x_ref, s_ref, b_ref, o_ref, *, act: str):
    """One [br, c] block: y = act(x * scale + shift), scale/shift
    [1, c] broadcast down the rows; the casts mirror the XLA reference
    (normalization.py) — results match to float rounding (<= 1 ulp,
    the two programs may contract the multiply-add differently)."""
    x = x_ref[...]
    y = x * s_ref[...].astype(x.dtype) + b_ref[...].astype(x.dtype)
    if act == "relu":
        y = jnp.maximum(y, jnp.zeros((), y.dtype))
    o_ref[...] = y


def bn_act_reference(x, scale, shift, act: str = "relu"):
    """The XLA epilogue the kernel must match (and the function the
    backward recomputes through). jax.nn.relu, not jnp.maximum: its
    custom-jvp zero-at-zero subgradient is what the unfused BatchNorm
    path differentiates, so the recompute backward matches it exactly."""
    y = x * scale.astype(x.dtype) + shift.astype(x.dtype)
    if act == "relu":
        y = jax.nn.relu(y)
    return y


def _bn_act_impl(x, scale, shift, act, block_rows, interpret):
    c = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= int(s)
    x2 = x.reshape(rows, c)
    s2 = scale.reshape(1, c)
    b2 = shift.reshape(1, c)
    out = pl.pallas_call(
        functools.partial(_bn_act_kernel, act=act),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, c), x.dtype),
        name=kernel_name("bn_act", x.dtype, rows=rows, c=c, br=block_rows),
        interpret=interpret,
    )(x2, s2, b2)
    return out.reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def bn_act(x, scale, shift, act: str = "relu", block_rows: int = 8,
           interpret: bool = False):
    """Fused BatchNorm epilogue y = act(x * scale + shift) over channels-
    last x, one HBM read + one write. act in ('relu', 'identity');
    block_rows from pick_bn_block (rows must divide). Gradients are
    exact: the backward is jax.vjp through bn_act_reference."""
    return _bn_act_impl(x, scale, shift, act, block_rows, interpret)


def _bn_act_vjp_fwd(x, scale, shift, act, block_rows, interpret):
    return _bn_act_impl(x, scale, shift, act, block_rows, interpret), (
        x, scale, shift)


def _bn_act_vjp_bwd(act, block_rows, interpret, res, g):
    x, scale, shift = res
    _, vjp = jax.vjp(
        lambda xx, ss, hh: bn_act_reference(xx, ss, hh, act),
        x, scale, shift)
    return vjp(g)


bn_act.defvjp(_bn_act_vjp_fwd, _bn_act_vjp_bwd)


def fused_affine_act(x, scale, shift, act: str):
    """y = act(x * scale + shift) through `bn_act`, or None when the
    epilogue stays on XLA (fused into the producing conv by the
    compiler): OPT-IN (`convbn_mode` forced), act relu or identity, a
    block plan for the per-device rows under a data mesh. scale/shift
    pass through untouched (f32 in normal runs, f64 under x64 gradient
    checks); the kernel casts to x.dtype exactly as the XLA path does."""
    if act not in ("relu", "identity") or x.ndim < 2:
        return None
    if not (convbn_mode() == "forced" and helpers_enabled()):
        return None
    b_dev = kernel_call.per_device_batch(x.shape[0])
    br = pick_bn_block((b_dev,) + tuple(x.shape[1:]), x.dtype) if b_dev else 0
    if not br:
        return None
    interp = kernel_call.interpret()
    return kernel_call.per_batch_shard(
        lambda x_, s_, h_: bn_act(x_, s_, h_, act, br, interp),
        (x, scale, shift), (True, False, False))
