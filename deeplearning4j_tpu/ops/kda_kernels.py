"""The per-channel delta rule's chunks as one Pallas kernel pair (the door
the layer calls is `ops.delta.kda_chunks`).

The contract is `nn/layers/hybrid.py` `chunk_channel_gated_delta_rule`'s:
q, k, g [n, r, h, c, dk], v [.., dv], beta [n, r, h, c], float32 and
chunk-major (`to_chunks`) -> (o [.., dv], the states the chunks start from
[n, r, h, dk, dv]). Per head S_0 = 0 and for every token
S <- Diag(exp(g)) S; S <- S + k (beta (v - S^T k))^T; o = S^T q.

`dl4j_kda_fwd_*` walks a head's chunks in order with the state in a VMEM
scratch; `dl4j_kda_bwd_*` walks them back with the state's cotangent there.
A chunk's running decay, its level factors, both score matrices, the
inverse, U, W, d and the state update never leave VMEM in the forward. A
forward that a backward follows (under the row groups' checkpoint: the
rerun) also writes each chunk's scores, inverse and [U | W] — 0.9 of the
inputs' bytes, alive inside one row group's backward only — and the backward
reads them in place of forming them again; everything else of a chunk it
recomputes from the inputs and the chunk-start state. The two kernels share
no logic with the XLA form, which stays the fallback and the oracle of the
tests: that one wants whole-batch products, this one a chunk at a time.
What does not depend on how the rule decays — the inverse, the state's
stage, the first stages of the backward, the plan — is `ops/chunk_kernels.py`,
shared with the scalar rule's pair (`ops/gdn_kernels.py`); here are the
per-channel decays, the scores made of them and their backward.

A program takes `_HEADS` heads of one chunk of one row, and its heads go
through every stage SIDE BY SIDE (`_side_by_side`): a chunk is a chain of
some twenty products of [64, 64 .. 256] each waiting for the one before,
which is latency and not work — one head a time it takes 3.4 us where its
bundles count 1.2; eight chains emitted stage by stage fill each other's
waits (`PERF.md` section 6, PR 34: 13.8 -> 3.8 ms a row forward).

A chunk in the kernel (c = 64 tokens, G the running sum of g inside it):

  decays   every exponent that is formed is <= 0, as in the XLA form. The
           pairs l < j of a chunk fall into levels by the highest bit p in
           which j and l differ (p = 32 .. 1): j lies in the later half of a
           block of 2p tokens, l in the earlier, and both are referred to the
           later half's first row r,  E_jld = e^(G_jd - r_d) e^(r_d - G_ld).
           For a row i that is F_p(i) = e^-|G_i - G_r(i)| whichever side it
           is on, so a level costs ONE exp a token and channel and one
           product (k F_p) [k F_p; q F_p]^T on the MXU, kept where the
           level's mask is. No pair terms [., ., d] are formed and no sum
           over channels is a lane reduction a pair; the diagonal (e^0) is a
           row sum of q k.
  layout   every [c, c] matrix is held TRANSPOSED ([l, j] for l <= j): a
           level's K K^T and (Q K^T)^T then come side by side out of one
           product whose result fills all 128 lanes, and beta scales A's
           columns as the row it arrives as.
  G        the running sum and the three finest levels' differences at once,
           C g with C a constant of 0 / +-1 (exact in bfloat16) and g split
           into three bfloat16 parts: float32-exact, as `jnp.cumsum` is; the
           coarse levels' differences from G by whole sublane tiles.
  solve    T = (I + A)^-1 in the product form over nilpotent factors: the
           four diagonal blocks of 16 by (I - N)(I + N^2)(I + N^4)(I + N^8),
           the blocks beside them by (I + M^2)(I - M), M = R T_d; every
           product with both operands split in two (three bfloat16 passes),
           and so T's products with the right-hand side and, backwards, with
           its cotangent (XLA's triangular solve runs at the highest
           precision).
  rest     [U | W] = T [beta v | -beta k e^G], d = U + W S,
           o = (q e^G) S + P d, S <- e^(G_last) S + (k e^(G_last - G))^T d
           at the policy's precision (`linear._precision()`), as the XLA
           form's `_mm`: the default — one MXU pass of float32 operands —
           under the mixed policy, else the highest.

The state lives transposed ([dv, dk]) in the kernels so that its decay is a
product along lanes; `ops.delta.kda_chunks` hands it out as [dk, dv].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.ops.chunk_kernels import (
    _NN, _NT, _TN, BF16, CHUNK, F32, _after_scores, _bwd_stages, _dot, _dot3, _dot_const, _keep,
    _kept, _last_row, _names, _pairs, _params, _plan, _side_by_side)

#: the levels (of `CHUNK` // 2 .. 1 tokens a half) whose differences of G come
#: from whole sublane tiles on the vector unit; the finer ones from the MXU
_COARSE = 3


@functools.lru_cache(maxsize=None)
def _constants(c: int = CHUNK):
    """(cmat [(1 + fine levels) c, c] of 0 / +-1, lev [c, 2c] int32). cmat's
    first c rows are the running sum (lower-triangular ones); each further
    block, for a level p < 8, gives G_i - G_r(i) with r(i) the first row of
    the later half of i's block of 2p (the levels p >= 8 take theirs from G by
    whole tiles: `_differences`). lev names the pairs' levels (0: p = c / 2 ..
    5: p = 1), as `chunk_kernels._pairs` gives them."""
    i = np.arange(c)
    tri, lev2 = _pairs(c)
    blocks = [tri]
    for lvl in range(_COARSE, c.bit_length() - 1):
        p = c >> (lvl + 1)
        blocks.append(tri - tri[(i // (2 * p)) * 2 * p + p])
    return np.concatenate(blocks, 0), lev2


# ---------------------------------------------------------------------------
# one chunk of one head
# ---------------------------------------------------------------------------
def _differences(g, cm, highest: bool):
    """(G [c, d], [G_i - G_r(i) a level]) for g [c, d]: the running sum and
    the fine levels' differences in one exact product with the constant; the
    coarse levels' by subtracting a row of G from whole tiles."""
    c = g.shape[0]
    dall = _dot_const(cm, g, _NN, highest)
    gc = dall[:c]
    diffs = []
    for lvl in range(_COARSE):
        p = c >> (lvl + 1)
        blocks = gc.reshape(c // (2 * p), 2 * p, gc.shape[1])
        diffs.append((blocks - blocks[:, p:p + 1]).reshape(gc.shape))
    return gc, diffs + [dall[(i + 1) * c:(i + 2) * c] for i in range(cm.shape[0] // c - 1)]


def _decays(g, cm, highest: bool):
    """(G [c, d], [F_p = e^-|G_i - G_r(i)| a level], e^G, e^(G_last - G),
    G_last [1, d], the mask of the last row) of a chunk's g [c, d]."""
    gc, diffs = _differences(g, cm, highest)
    last, g_last = _last_row(gc)
    return gc, [jnp.exp(-jnp.abs(x)) for x in diffs], jnp.exp(gc), jnp.exp(g_last - gc), g_last, last


def _scores(q, k, f, lev2, highest: bool):
    """[K K^T decayed, strictly upper | (Q K^T)^T decayed, upper with its
    diagonal] [c, 2c]: every [c, c] matrix is held TRANSPOSED ([l, j] for the
    pair l <= j), so a level's two products come side by side out of ONE
    with all 128 lanes of its result in use."""
    c = q.shape[0]
    z = jnp.zeros((c, 2 * c), F32)
    for i in range(len(f)):
        kf = k * f[i]
        prod = _dot3 if i >= 2 else _dot    # below 16 tokens the XLA form sums in float32
        z = jnp.where(lev2 == i, prod(kf, jnp.concatenate([kf, q * f[i]], 0), _NT, highest), z)
    diag = lev2[:, c:] == len(f)
    return jnp.concatenate(
        [z[:, :c], jnp.where(diag, jnp.sum(q * k, axis=1, keepdims=True), z[:, c:])], 1)


def _score_stages(q, k, g, cm, lev2, highest: bool):
    """The first stage of a chunk forward, a generator for `_side_by_side`:
    -> (G, the scores)."""
    gc, f, *_ = _decays(g, cm, highest)
    yield
    yield gc, _scores(q, k, f, lev2, highest)


def _bwd_head(q, k, v, g, brow, st, kept, do, dst, cm, lev2, highest: bool):
    """One chunk backward: st the state the chunk starts from, dst the
    cotangent of the state it ends with (both [dv, dk]), do [c, dv] ->
    (dq, dk, dv, dg [c, .], dbeta as a row [1, c], the cotangent of st). A
    generator: it yields where a product's result is waited for, so that
    `_side_by_side` can emit several heads' stages in turn; its last yield
    is the result."""
    c = q.shape[0]
    gc, f, since, to_end, g_last, last = _decays(g, cm, highest)
    yield
    (diag, bcol, qd, kd, ks, decay, dqd, dp_t, dkd, ddecay, dst_new, da_t, drv, dbeta_row,
     dks) = yield from _bwd_stages(q, k, v, brow, st, kept, do, dst, lev2[:, c:], since, to_end,
                                   g_last, highest)

    # the decayed scores' backward, level by level: da and dk are the same
    # masked products the other way round, and dG = a da - k dk
    w2 = jnp.concatenate([da_t * brow, dp_t], 1)                         # [c, 2c]
    dcol = jnp.sum(_keep(diag, dp_t), axis=1, keepdims=True)
    rows, cols = jnp.concatenate([jnp.zeros_like(k), dcol * k], 0), dcol * q
    for i in range(len(f)):
        prod = _dot3 if i >= 2 else _dot
        wl = _keep(lev2 == i, w2)
        kf = k * f[i]
        rows = rows + jnp.concatenate([f[i], f[i]], 0) * prod(wl, kf, _TN, highest)
        cols = cols + f[i] * prod(wl, jnp.concatenate([kf, q * f[i]], 0), _NN, highest)
    dk_a, dq_s = rows[:c], rows[c:]
    yield

    dgc = q * dq_s + k * (dk_a - cols) + dqd * qd + dks * ks - dkd * kd
    dg_last = jnp.sum(dkd * kd, axis=0, keepdims=True) + decay * ddecay
    dgc = dgc + _keep(last, dg_last)
    dg = _dot_const(cm[:c], dgc, _TN, highest)                           # the sum run backwards
    dq = dq_s + dqd * since
    dk_ = dk_a + cols + dks * since + dkd * to_end
    yield dq, dk_, drv * bcol, dg, dbeta_row, dst_new


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
def _kda_fwd_kernel(cm_ref, lev_ref, q_ref, k_ref, v_ref, g_ref, beta_ref,
                    o_ref, s_ref, *rest, highest: bool):
    """One (row, group of heads, chunk) program; the chunks of a head come
    in order and st_ref [heads, dv, dk] carries their state. Writes o and the
    state the chunk STARTED from (transposed) — and, for a backward that will
    follow, the chunk's scores, inverse and [U | W]. The scores of every
    head side by side, then the skeleton's `_after_scores`: the inverses of
    all, then the state's stage."""
    *kept_refs, st_ref, gc_ref, scores_ref, t_ref = rest
    heads, c = q_ref.shape[:2]

    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    cm, lev2 = cm_ref[...], lev_ref[...]
    lev = lev2[:, c:]
    js = range(heads)

    got = _side_by_side(_score_stages, [
        (q_ref[j], k_ref[j], g_ref[j], cm, lev2, highest) for j in js])
    for j, (gc, sc) in zip(js, got):
        gc_ref[j], scores_ref[j] = gc, sc

    _after_scores(lambda j: q_ref[j], lambda j: k_ref[j], lambda j: gc_ref[j], v_ref, beta_ref,
                  o_ref, s_ref, kept_refs, st_ref, scores_ref, t_ref, lev, highest)


def _kda_bwd_kernel(cm_ref, lev_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref,
                    scores_ref, t_ref, uw_ref, do_ref,
                    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dst_ref, *, highest: bool):
    """The same programs with the chunks of a head in REVERSE order;
    dst_ref [heads, dv, dk] carries the state's cotangent."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    cm, lev2 = cm_ref[...], lev_ref[...]
    js = range(q_ref.shape[0])
    got = _side_by_side(_bwd_head, [
        (q_ref[j], k_ref[j], v_ref[j], g_ref[j], beta_ref[pl.ds(j, 1), :], s_ref[j],
         (scores_ref[j], t_ref[j], uw_ref[j]), do_ref[j], dst_ref[j], cm, lev2, highest)
        for j in js])
    for j, (dq, dk, dv, dg, dbeta, dst) in zip(js, got):
        dq_ref[j], dk_ref[j], dv_ref[j], dg_ref[j], dst_ref[j] = dq, dk, dv, dg, dst
        dbeta_ref[pl.ds(j, 1), :] = dbeta


def _kda_fwd(q, k, v, g, beta, *, keep: bool, highest: bool, interpret: bool):
    n, r, h, c, dk = q.shape
    dv = v.shape[-1]
    cm, lev2 = _constants(c)
    grid, hg, tokens, bspec, sspec, const = _plan(n, r, h, c, dk, dv, reverse=False)
    kept = _kept(c, dk, dv) if keep else ()
    return pl.pallas_call(
        functools.partial(_kda_fwd_kernel, highest=highest),
        out_shape=(jax.ShapeDtypeStruct((n, r, h, c, dv), F32),
                   jax.ShapeDtypeStruct((n, r, h, dv, dk), F32))
        + tuple(jax.ShapeDtypeStruct((n, r, h, c, w), F32) for w in kept),
        grid=grid,
        in_specs=[const(cm.shape), const(lev2.shape), tokens(dk), tokens(dk), tokens(dv),
                  tokens(dk), bspec],
        out_specs=(tokens(dv), sspec) + tuple(tokens(w) for w in kept),
        scratch_shapes=[pltpu.VMEM((hg, dv, dk), F32), pltpu.VMEM((hg, c, dk), F32),
                        pltpu.VMEM((hg, c, 2 * c), F32), pltpu.VMEM((hg, c, c), F32)],
        name=pk.kernel_name("kda_fwd", F32, **_names(n, r, h, c, dk, dv)),
        interpret=interpret,
        compiler_params=_params(hg, c, dk, dv, 7 + 3 * keep),
    )(jnp.asarray(cm, BF16), jnp.asarray(lev2), q, k, v, g, beta)


def _kda_bwd(q, k, v, g, beta, st, scores, t_t, uw, do, *, highest: bool, interpret: bool):
    n, r, h, c, dk = q.shape
    dv = v.shape[-1]
    cm, lev2 = _constants(c)
    grid, hg, tokens, bspec, sspec, const = _plan(n, r, h, c, dk, dv, reverse=True)
    return pl.pallas_call(
        functools.partial(_kda_bwd_kernel, highest=highest),
        out_shape=tuple(jax.ShapeDtypeStruct(a.shape, F32) for a in (q, k, v, g, beta)),
        grid=grid,
        in_specs=[const(cm.shape), const(lev2.shape), tokens(dk), tokens(dk), tokens(dv),
                  tokens(dk), bspec, sspec] + [tokens(w) for w in _kept(c, dk, dv)]
        + [tokens(dv)],
        out_specs=(tokens(dk), tokens(dk), tokens(dv), tokens(dk), bspec),
        scratch_shapes=[pltpu.VMEM((hg, dv, dk), F32)],
        name=pk.kernel_name("kda_bwd", F32, **_names(n, r, h, c, dk, dv)),
        interpret=interpret,
        compiler_params=_params(hg, c, dk, dv, 12),
    )(jnp.asarray(cm, BF16), jnp.asarray(lev2), q, k, v, g, beta, st, scores, t_t, uw, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def kda_chunk_kernels(q, k, v, g, beta, highest: bool, interpret: bool):
    """(o [n, r, h, c, dv], the chunk-start states TRANSPOSED
    [n, r, h, dv, dk]) through the kernel pair. The states are the backward's
    residual and a counter's input: no cotangent flows through them. A
    forward that a backward follows also keeps each chunk's scores, inverse
    and [U | W] (0.9 of the inputs' bytes) for it."""
    return _kda_fwd(q, k, v, g, beta, keep=False, highest=highest, interpret=interpret)


def _kda_vjp_fwd(q, k, v, g, beta, highest, interpret):
    o, st, *kept = _kda_fwd(q, k, v, g, beta, keep=True, highest=highest, interpret=interpret)
    return (o, st), (q, k, v, g, beta, st, *kept)


def _kda_vjp_bwd(highest, interpret, res, cts):
    return _kda_bwd(*res, cts[0], highest=highest, interpret=interpret)


kda_chunk_kernels.defvjp(_kda_vjp_fwd, _kda_vjp_bwd)
