"""What any chunked delta rule's Pallas kernels need, whichever way the rule
decays its state: the products at their precisions, the inverse of a chunk's
unit-triangular system, the stage in which the state comes in, the first
stages of a chunk's backward (the state's, the read-out's and the solve's),
the emission of several heads' stages side by side, the grid and block plan,
and the kernels' names. The rules' own arithmetic — how a chunk's decayed
scores are made and taken back — lives with each rule: `ops/kda_kernels.py`
(a decay a key channel), `ops/gdn_kernels.py` (one a head).

Every [c, c] matrix of a chunk is held TRANSPOSED ([l, j] for the pair
l <= j); `lev` [c, c] int32 names the pairs: inside a diagonal block of 16
the levels 2 to 5, the diagonal 6, below it 7. The state lives transposed
([dv, dk]) in the kernels.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32, BF16 = jnp.float32, jnp.bfloat16

#: tokens a chunk the kernels are written for (six levels of halves)
CHUNK = 64
#: heads a program where the head count allows it: a multiple of 8, so that
#: beta's block [heads, c] is whole tiles
_HEADS = 8

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


@functools.lru_cache(maxsize=None)
def _pairs(c: int = CHUNK):
    """(tri [c, c] float32, lev [c, 2c] int32): the lower-triangular ones (a
    running sum as a product) and the pairs of a chunk's tokens TRANSPOSED, as
    the kernels hold every [c, c] matrix: lev[l, j] for the pair l < j is the
    index of the highest bit in which j and l differ, counted down (0: they
    lie in different halves of the chunk .. 5: neighbours), 6 on the
    diagonal, 7 below it; columns c.. the same again, columns ..c with the
    diagonal left out (7)."""
    i = np.arange(c)
    tri = (i[:, None] >= i[None, :]).astype(np.float32)
    x = i[:, None] ^ i[None, :]
    lev = np.where(i[:, None] < i[None, :],
                   (c.bit_length() - 2) - np.floor(np.log2(np.maximum(x, 1))).astype(np.int32), 7)
    lev = np.where(i[:, None] == i[None, :], 6, lev).astype(np.int32)
    return tri, np.concatenate([np.where(lev == 6, 7, lev), lev], 1)


# ---------------------------------------------------------------------------
# products inside the kernels
# ---------------------------------------------------------------------------
def _dot(a, b, dims, highest: bool):
    """A product of float32 operands at the policy's precision, as the XLA
    form's `_mm`: the highest, or the default — ONE pass of the MXU on a
    TPU, float32 wherever the default is (the interpreter on the CPU)."""
    return lax.dot_general(a, b, dims, preferred_element_type=F32,
                           precision=lax.Precision.HIGHEST if highest else None)


def _keep(mask, a):
    """a where the mask holds, zero elsewhere (a float32 zero: under
    `jax_enable_x64` a Python scalar would enter the kernel as float64)."""
    return jnp.where(mask, a, jnp.zeros_like(a))


def _split(a):
    hi = a.astype(BF16)
    return hi, (a - hi.astype(F32)).astype(BF16)


def _dot3(a, b, dims, highest: bool):
    """A product with both operands split in two bfloat16 parts: three passes,
    an error of ~2^-16 of the terms where one pass leaves 2^-8."""
    if highest:
        return _dot(a, b, dims, True)
    (ah, al), (bh, bl) = _split(a), _split(b)
    f = lambda x, y: lax.dot_general(x, y, dims, preferred_element_type=F32)  # noqa: E731
    return f(ah, bh) + (f(ah, bl) + f(al, bh))


def _dot_const(cm, x, dims, highest: bool, const_first: bool = True):
    """cm x (or x cm) for a constant cm of 0 / +-1 held in bfloat16, exact to
    float32: x in three bfloat16 parts, three passes."""
    both = lambda c_, y: (c_, y) if const_first else (y, c_)  # noqa: E731
    if highest:
        return _dot(*both(cm.astype(F32), x), dims, True)
    x1 = x.astype(BF16)
    r = x - x1.astype(F32)
    x2 = r.astype(BF16)
    x3 = (r - x2.astype(F32)).astype(BF16)
    f = lambda y: lax.dot_general(*both(cm, y), dims, preferred_element_type=F32)  # noqa: E731
    return f(x1) + (f(x2) + f(x3))


# ---------------------------------------------------------------------------
# one chunk of one head
# ---------------------------------------------------------------------------
def _last_row(gc):
    """(the mask of the last row, that row [1, d]) of G [c, d]."""
    last = lax.broadcasted_iota(jnp.int32, gc.shape, 0) == gc.shape[0] - 1
    return last, jnp.sum(_keep(last, gc), axis=0, keepdims=True)


def _column(brow, lev):
    """A row [1, c] as a column [c, 1]."""
    return jnp.sum(_keep(lev == 6, brow), axis=1, keepdims=True)


def _inverses(a_ts, lev, highest: bool):
    """(I + a_t)^-1 for each strictly upper-triangular a_t [m, m] of a list
    (`lev` [m, m] names the pairs inside a diagonal block of 16 — levels 2 to
    5 — and the diagonal, 6). The diagonal blocks N by
    (I - N)(I + N^2)(I + N^4)(I + N^8) — powers of one matrix commute, so a
    squaring and the product it feeds share their left operand and run as ONE
    product [N^2 T | N^2 N^2]; the blocks beside them by
    T_d (I + M^2)(I - M), M = R T_d, which is nilpotent of order 4. Eight
    products one after the other, each waiting out the MXU's latency: the
    list's chains are independent and are emitted step by step side by side,
    so that one's wait is the others' work."""
    m_ = lev.shape[0]
    eye = (lev == 6).astype(F32)
    inner = (lev >= 2) & (lev < 6)
    mm = lambda x, y: _dot3(x, y, _NN, highest)  # noqa: E731
    nd = [_keep(inner, a) for a in a_ts]
    td, power = [eye - n for n in nd], [mm(n, n) for n in nd]
    for _ in range(2):
        both = [mm(p, jnp.concatenate([t, p], 1)) for p, t in zip(power, td)]
        td, power = ([t + x[:, :m_] for t, x in zip(td, both)], [x[:, m_:] for x in both])
    td = [t + mm(p, t) for p, t in zip(power, td)]
    m = [mm(a - n, t) for a, n, t in zip(a_ts, nd, td)]
    m2 = [mm(x, x) for x in m]
    y = [t + mm(t, x) for t, x in zip(td, m2)]
    return [mm(x, eye - z) for x, z in zip(y, m)]


def _beside(x, y, fill):
    """[[x, fill], [fill, y]]: two [c, c] matrices as the diagonal blocks of
    one [2c, 2c]."""
    pad = jnp.full(x.shape, fill, x.dtype)
    return jnp.concatenate([jnp.concatenate([x, pad], 1), jnp.concatenate([pad, y], 1)], 0)


def _state_stages(q, k, v, brow, gc, scores, t_t, st, lev, highest: bool):
    """The last stage of a chunk forward, a generator for `_side_by_side`: the
    writes [U | W] = T [beta v | -beta k e^G] at the solve's precision, then
    the state comes in — st the state the chunk starts from, transposed
    [dv, dk] -> (o [c, dv], the state the chunk ends with, [U | W])."""
    c, dv = q.shape[0], v.shape[1]
    since, (_, g_last) = jnp.exp(gc), _last_row(gc)
    bcol = _column(brow, lev)
    uw = _dot3(t_t, jnp.concatenate([v * bcol, (k * since) * -bcol], 1), _TN, highest)
    yield
    ws = _dot(jnp.concatenate([uw[:, dv:], q * since], 0), st, _NT, highest)   # [2c, dv]
    yield
    d = uw[:, :dv] + ws[:c]
    o = ws[c:] + _dot(scores[:, c:], d, _TN, highest)
    yield o, st * jnp.exp(g_last) + _dot(d, k * jnp.exp(g_last - gc), _TN, highest), uw

def _bwd_stages(q, k, v, brow, st, kept, do, dst, lev, since, to_end, g_last, highest: bool):
    """The first stages of one chunk backward, whichever way the rule decays:
    the state's, the read-out's and the solve's. st the state the chunk
    starts from, dst the cotangent of the state it ends with (both [dv, dk]),
    do [c, dv], kept = the forward's (scores, inverse, [U | W]); `since` the
    decay since the chunk's start, `to_end` up to its end, `g_last` the log
    decay over the whole chunk — [c, dk] and [1, dk], or one column and one
    number a head. A generator a rule's backward delegates to (`yield from`):
    it yields where a product's result is waited for and RETURNS
    (diag, bcol, qd, kd, ks, decay, dqd, dp_t, dkd, ddecay, dst_new, da_t,
    drv, dbeta_row, dks): the masks' diagonal, beta as a column, the decayed
    q and k (q since, k to_end, k since), e^g_last, the cotangents of qd, of
    the decayed (Q K^T)^T (upper with its diagonal), of kd, of e^g_last
    (summed over the state's rows [1, dk]), the cotangent of st, the
    cotangent of A^T (strictly upper), of beta v (T^T du), beta's as a row
    [1, c] and the cotangent of ks."""
    c = q.shape[0]
    dv_ = v.shape[1]
    scores, t_t, uw = kept
    diag = lev == 6
    bcol = _column(brow, lev)
    qd, kd, ks = q * since, k * to_end, k * since
    p_t, kk_t = scores[:, c:], scores[:, :c]
    u, w = uw[:, :dv_], uw[:, dv_:]
    d = u + _dot(w, st, _NT, highest)
    decay = jnp.exp(g_last)                                              # [1, dk]
    dqd = _dot(do, st, _NN, highest)                                     # [c, dk]
    dd = _dot(p_t, do, _NN, highest) + _dot(kd, dst, _NT, highest)       # [c, dv]
    yield
    dp_t = _keep(lev <= 6, _dot(d, do, _NT, highest))                    # [c, c], upper
    dkd = _dot(d, dst, _NN, highest)                                     # [c, dk]
    ddecay = jnp.sum(st * dst, axis=0, keepdims=True)                    # [1, dk]
    dw = _dot(dd, st, _NN, highest)                                      # [c, dk]
    dst_new = dst * decay + _dot(jnp.concatenate([do, dd], 0),
                                 jnp.concatenate([qd, w], 0), _TN, highest)
    yield
    # the solve's own backward, at the solve's precision (XLA's runs at the highest)
    drhs = _dot3(t_t, jnp.concatenate([dd, dw], 1), _NN, highest)        # T^T [du | dw]
    yield
    da_t = _keep(lev < 6, -_dot3(uw, drhs, _NT, highest))           # strictly upper
    drv, drw = drhs[:, :dv_], drhs[:, dv_:]
    dbeta = jnp.sum(drv * v, axis=1, keepdims=True) - jnp.sum(drw * ks, axis=1, keepdims=True)
    dbeta_row = (jnp.sum(da_t * kk_t, axis=0, keepdims=True)
                 + jnp.sum(_keep(diag, dbeta), axis=0, keepdims=True))
    dks = drw * -bcol
    yield
    return diag, bcol, qd, kd, ks, decay, dqd, dp_t, dkd, ddecay, dst_new, da_t, drv, dbeta_row, dks


def _side_by_side(stages, args):
    """Run one generator of `stages` an element of `args` in turn, a stage
    each (`zip` advances them round by round), until all are done: their
    last yields. Independent chains of dependent products, emitted side by
    side, fill each other's waits."""
    for out in zip(*(stages(*a) for a in args)):
        pass
    return out


def _after_scores(q_of, k_of, g_of, v_ref, beta_ref, o_ref, s_ref, kept_refs, st_ref, scores_ref,
                  t_ref, lev, highest: bool):
    """What a forward program does once each of its heads' decayed scores lie
    in scores_ref [heads, c, 2c]: the inverses of all, two heads the diagonal
    blocks of one matrix and the pairs' chains side by side (the chain of
    eight dependent products is latency, not work); then the state's stage,
    the heads side by side. q_of(j), k_of(j) [c, dk] and g_of(j) (G [c, dk],
    or one column) are head j's; st_ref [heads, dv, dk] carries the state.
    Writes o and the state the chunk STARTED from (transposed) — and, for a
    backward that will follow, the chunk's scores, inverse and [U | W]."""
    heads, c = scores_ref.shape[:2]
    js = range(heads)
    a_t = [scores_ref[j][:, :c] * beta_ref[j:j + 1, :] for j in js]
    if heads % 2 == 0:
        pairs = _inverses([_beside(a_t[j], a_t[j + 1], 0.0) for j in range(0, heads, 2)],
                          _beside(lev, lev, 7), highest)
        for j, t2 in enumerate(pairs):
            t_ref[2 * j], t_ref[2 * j + 1] = t2[:c, :c], t2[c:, c:]
    else:
        for j, t_t in enumerate(_inverses(a_t, lev, highest)):
            t_ref[j] = t_t

    got = _side_by_side(_state_stages, [
        (q_of(j), k_of(j), v_ref[j], beta_ref[pl.ds(j, 1), :], g_of(j), scores_ref[j],
         t_ref[j], st_ref[j], lev, highest) for j in js])
    for j, (o, st_new, uw) in zip(js, got):
        s_ref[j] = st_ref[j]
        o_ref[j], st_ref[j] = o, st_new
        for ref, a in zip(kept_refs, (scores_ref[j], t_ref[j], uw)):
            ref[j] = a


# ---------------------------------------------------------------------------
# the kernels' plan
# ---------------------------------------------------------------------------
def _plan(n: int, r: int, h: int, c: int, dk: int, dv: int, reverse: bool, group: int = 1):
    """(grid, heads a program, a block of `width` columns a head — of the
    program's heads, or of `heads` of an array that holds fewer —, beta's
    block, the state's block, the constants' blocks): a program takes
    `_HEADS` heads of one chunk of one row (all h where `_HEADS` does not
    divide h or is no multiple of `group`, the heads that share one of a
    narrower array's), the chunks innermost — in order, or backwards."""
    hg = _HEADS if h % _HEADS == 0 and _HEADS % group == 0 else h

    def at(ri, hi, ni):
        return (n - 1 - ni if reverse else ni), ri, hi

    def tokens(width, heads=hg):
        return pl.BlockSpec((None, None, heads, c, width), lambda *i: at(*i) + (0, 0))

    beta = pl.BlockSpec((None, None, hg, c), lambda *i: at(*i) + (0,))
    state = pl.BlockSpec((None, None, hg, dv, dk), lambda *i: at(*i) + (0, 0))
    const = lambda shape: pl.BlockSpec(shape, lambda *i: (0, 0))  # noqa: E731
    return (r, h // hg, n), hg, tokens, beta, state, const


def _params(hg: int, c: int, dk: int, dv: int, arrays: int):
    """The grid's order and the VMEM a program may take: `arrays` token
    blocks and two states a head, double-buffered, beside the scratch."""
    need = 2 * hg * 4 * (arrays * c * max(dk, dv) + 2 * dk * dv) + hg * 4 * dk * dv
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=min(max(2 * need, 32 * 2 ** 20), 110 * 2 ** 20))


def _names(n, r, h, c, dk, dv):
    """The shape in a kernel's name, the chunk count first: a name whose
    first dimension is `n<digits>` is one the benchmark's trace reader
    (`benchmark/trace_reduce.py` `KERNEL`) folds into its family, so a step's
    calls add up under `dl4j_kda_fwd`, `dl4j_gdn_bwd`, .. in `device_ops`.
    `h` is the head count as the name shall carry it (`32`, or `32k16` for
    32 value heads over 16 key heads)."""
    return dict(n=n, r=r, h=h, c=c, d=dk) if dk == dv else dict(n=n, r=r, h=h, c=c, d=dk, dv=dv)


def _kept(c: int, dk: int, dv: int):
    """Widths of what a forward keeps for its backward, a chunk and head:
    the scores [c, 2c], the inverse [c, c], [U | W] [c, dv + dk]."""
    return 2 * c, c, dv + dk
