"""The gated delta rule's chunks — ONE decay a head and token — as one Pallas
kernel pair (the door the layer calls is `ops.delta.gdn_chunks`).

The contract is `nn/layers/hybrid.py` `chunk_gated_delta_rule`'s: q, k
[n, r, hk, c, dk] (normalised and scaled by the caller), v [n, r, hv, c, dv],
g (log decay <= 0) and beta [n, r, hv, c], float32 and chunk-major
(`to_chunks`) -> o [n, r, hv, c, dv]. Value head j reads key head
j // (hv / hk). Per value head S_0 = 0 and for every token
S <- exp(g) S; S <- S + k (beta (v - S^T k))^T; o = S^T q.

`dl4j_gdn_fwd_*` walks a head's chunks in order with the state in a VMEM
scratch; `dl4j_gdn_bwd_*` walks them back with the state's cotangent there.
A chunk's decay mask, scores, inverse, U, W, d and the state update never
leave VMEM in the forward; the XLA form writes the chunk's A, B, S, the
solve's sides and their cotangents (268 MB a row each at 8192 tokens) and
reads them back. A forward that a backward follows (under the row groups'
checkpoint: the rerun) also writes each chunk's scores, inverse and [U | W]
a value head, and the backward reads them in place of forming them again.
The skeleton — the inverse, the state's stage, the first stages of the
backward, heads side by side, the plan — is `ops/chunk_kernels.py`, shared
with the per-channel rule's pair (`ops/kda_kernels.py`); here is what one
scalar decay a head makes of a chunk's scores, and takes back.

A chunk in the kernel (c = 64 tokens, G the running sum of g inside it):

  G        a ROW a head, all heads of a program in one product
           g [heads, c] x the triangle of ones, g split into three bfloat16
           parts: float32-exact, as `jnp.cumsum` is; a column where a stage
           wants one.
  mask     M[l, j] = exp(G_j - G_l) for l <= j and 0 elsewhere, TRANSPOSED as
           every [c, c] matrix of the skeleton; the exponent is masked to
           <= 0 before the exp, as in the XLA form.
  scores   k [k ; q]^T -> [c, 2c] ONCE a key head, at the policy's precision
           (`linear._precision()`, as the XLA form's `_mm`), then a value
           head: that x [M strictly upper | M with its diagonal]. q and k
           are never repeated to hv heads, in HBM or in VMEM.
  rest     the skeleton's: T = (I + A)^-1 at three bfloat16 passes,
           [U | W] = T [beta v | -beta k e^G], d = U + W S,
           o = (q e^G) S + P d, S <- e^(G_last) S + (k e^(G_last - G))^T d.
  back     the skeleton's stages, then: the scores' cotangent x the mask is
           the cotangent of k [k ; q]^T — summed over the value heads of a
           key head inside the program, two products a KEY head give dq and
           dk —, and x the scores that of the exponents: its column sums less
           its row sums are dG, one [c] vector a head; dg the running sum run
           backwards, again one exact product for all heads.
"""
from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.ops.chunk_kernels import (
    _NN, _NT, _TN, BF16, F32, _after_scores, _bwd_stages, _column, _dot, _dot_const, _keep, _kept,
    _last_row, _names, _pairs, _params, _plan, _side_by_side)


# ---------------------------------------------------------------------------
# one chunk of one head
# ---------------------------------------------------------------------------
def _running_sums(g, tri, highest: bool):
    """G [heads, c]: the running sum of g [heads, c] along the chunk, every
    head a row of ONE product with the triangle of ones — float32-exact."""
    return _dot_const(tri, g, _NT, highest, const_first=False)


def _decay_mask(grow, lev2):
    """(G as a column [c, 1], [M strictly upper | M with its diagonal]
    [c, 2c]) for a head's G as a row [1, c]: M[l, j] = exp(G_j - G_l) for
    l <= j, the exponent masked to <= 0 before the exp."""
    c = lev2.shape[0]
    gcol = _column(grow, lev2[:, c:])
    upper = lev2 <= 6
    return gcol, _keep(upper, jnp.exp(_keep(upper, jnp.concatenate([grow, grow], 1) - gcol)))


def _key_scores(q, k, highest: bool):
    """[K K^T | K Q^T] [c, 2c] of a key head: both of its score matrices,
    transposed, out of ONE product whose result fills all 128 lanes."""
    return _dot(k, jnp.concatenate([k, q], 0), _NT, highest)


def _bwd_head(q, k, v, grow, brow, st, kept, do, dst, lev2, highest: bool):
    """One chunk backward of one VALUE head: q, k its key head's, grow its G
    as a row [1, c], st the state the chunk starts from, dst the cotangent of
    the state it ends with (both [dv, dk]), do [c, dv] -> (the cotangent of
    its key head's k [k ; q]^T [c, 2c], what the decayed q and the decayed k
    add to dq and dk [c, dk] each, dv, dG as a row [1, c], dbeta as a row,
    the cotangent of st). A generator for `_side_by_side`."""
    c = q.shape[0]
    lev = lev2[:, c:]
    gcol, m2 = _decay_mask(grow, lev2)
    last, g_last = _last_row(gcol)
    since, to_end = jnp.exp(gcol), jnp.exp(g_last - gcol)
    yield
    (diag, bcol, qd, kd, ks, decay, dqd, dp_t, dkd, ddecay, dst_new, da_t, drv, dbeta_row,
     dks) = yield from _bwd_stages(q, k, v, brow, st, kept, do, dst, lev, since, to_end, g_last,
                                   highest)

    # the decayed scores' backward: scores = k [k ; q]^T x the mask, and the
    # mask's exponents are G_j - G_l
    w2 = jnp.concatenate([da_t * brow, dp_t], 1)                         # [c, 2c]
    e = w2 * kept[0]
    e = e[:, :c] + e[:, c:]                                              # [l, j]
    to_end_sum = jnp.sum(dkd * kd, axis=1, keepdims=True)                # [c, 1]
    dg_last = (jnp.sum(to_end_sum, axis=0, keepdims=True)
               + decay * jnp.sum(ddecay, axis=1, keepdims=True))         # [1, 1]
    dgcol = (jnp.sum(dqd * qd + dks * ks, axis=1, keepdims=True) - to_end_sum
             - jnp.sum(e, axis=1, keepdims=True) + _keep(last, dg_last))
    dgrow = jnp.sum(e, axis=0, keepdims=True) + jnp.sum(_keep(diag, dgcol), axis=0, keepdims=True)
    yield w2 * m2, dqd * since, dks * since + dkd * to_end, drv * bcol, dgrow, dbeta_row, dst_new


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
def _gdn_fwd_kernel(tri_ref, lev_ref, q_ref, k_ref, v_ref, g_ref, beta_ref,
                    o_ref, s_ref, *rest, highest: bool):
    """One (row, group of value heads, chunk) program; q_ref and k_ref hold
    the key heads the group reads. The chunks of a head come in order and
    st_ref [heads, dv, dk] carries their state. The scores of every head,
    then the skeleton's `_after_scores`."""
    *kept_refs, st_ref, grow_ref, scores_ref, t_ref = rest
    heads, c = scores_ref.shape[:2]
    rep = heads // q_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    lev2 = lev_ref[...]
    lev = lev2[:, c:]
    grow_ref[...] = _running_sums(g_ref[...], tri_ref[...], highest)
    kq = [_key_scores(q_ref[i], k_ref[i], highest) for i in range(heads // rep)]
    for j in range(heads):
        scores_ref[j] = kq[j // rep] * _decay_mask(grow_ref[pl.ds(j, 1), :], lev2)[1]

    _after_scores(lambda j: q_ref[j // rep], lambda j: k_ref[j // rep],
                  lambda j: _column(grow_ref[pl.ds(j, 1), :], lev), v_ref, beta_ref,
                  o_ref, s_ref, kept_refs, st_ref, scores_ref, t_ref, lev, highest)


def _gdn_bwd_kernel(tri_ref, lev_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref,
                    scores_ref, t_ref, uw_ref, do_ref,
                    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dst_ref, grow_ref, *, highest: bool):
    """The same programs with the chunks of a head in REVERSE order;
    dst_ref [heads, dv, dk] carries the state's cotangent. The value heads
    side by side, then two products a KEY head for dq and dk, then dg for
    all heads at once."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    heads, c = scores_ref.shape[:2]
    rep = heads // q_ref.shape[0]
    tri, lev2 = tri_ref[...], lev_ref[...]
    grow_ref[...] = _running_sums(g_ref[...], tri, highest)
    got = _side_by_side(_bwd_head, [
        (q_ref[j // rep], k_ref[j // rep], v_ref[j], grow_ref[pl.ds(j, 1), :],
         beta_ref[pl.ds(j, 1), :], s_ref[j], (scores_ref[j], t_ref[j], uw_ref[j]), do_ref[j],
         dst_ref[j], lev2, highest) for j in range(heads)])
    for j, (_, _, _, dv, dgrow, dbeta, dst) in enumerate(got):
        dv_ref[j], dst_ref[j] = dv, dst
        dbeta_ref[pl.ds(j, 1), :] = dbeta
        grow_ref[pl.ds(j, 1), :] = dgrow            # G is done with: the rows hold dG now
    for i in range(heads // rep):
        dkq, dq_dec, dk_dec = (functools.reduce(jnp.add, x)             # over its value heads
                               for x in zip(*(h[:3] for h in got[i * rep:(i + 1) * rep])))
        q, k = q_ref[i], k_ref[i]
        dk_a = _dot(dkq, jnp.concatenate([k, q], 0), _NN, highest)       # [c, dk]
        dkq_t = _dot(dkq, k, _TN, highest)                               # [2c, dk]
        dq_ref[i], dk_ref[i] = dkq_t[c:] + dq_dec, dk_a + dkq_t[:c] + dk_dec
    dg_ref[...] = _dot_const(tri, grow_ref[...], _NN, highest, const_first=False)  # the sum run backwards


def _calls(q, v, reverse: bool):
    """What both `pallas_call`s share for q [n, r, hk, c, dk] and v
    [n, r, hv, c, dv]: the skeleton's plan for hv heads in groups that hold
    whole key heads, `keys` a block of the key heads a program's value heads
    read, the constants (the triangle of ones, the pairs' levels) with their
    blocks, and the shape as the kernels' names carry it."""
    n, r, hk, c, dk = q.shape
    hv, dv = v.shape[2], v.shape[-1]
    grid, hg, tokens, rows, state, const = _plan(n, r, hv, c, dk, dv, reverse, group=hv // hk)
    tri, lev2 = _pairs(c)
    consts = (jnp.asarray(tri, BF16), jnp.asarray(lev2))
    return types.SimpleNamespace(
        grid=grid, heads=hg, tokens=tokens, keys=functools.partial(tokens, heads=hg * hk // hv),
        rows=rows, state=state, consts=consts, const_specs=[const(a.shape) for a in consts],
        names=_names(n, r, f"{hv}k{hk}", c, dk, dv), params=functools.partial(_params, hg, c, dk, dv))


def _gdn_fwd(q, k, v, g, beta, *, keep: bool, highest: bool, interpret: bool):
    c, dk = q.shape[3:]
    dv = v.shape[-1]
    p = _calls(q, v, reverse=False)
    kept = _kept(c, dk, dv) if keep else ()
    return pl.pallas_call(
        functools.partial(_gdn_fwd_kernel, highest=highest),
        out_shape=(jax.ShapeDtypeStruct(v.shape, F32),
                   jax.ShapeDtypeStruct(v.shape[:3] + (dv, dk), F32))
        + tuple(jax.ShapeDtypeStruct(v.shape[:4] + (w,), F32) for w in kept),
        grid=p.grid,
        in_specs=p.const_specs + [p.keys(dk), p.keys(dk), p.tokens(dv), p.rows, p.rows],
        out_specs=(p.tokens(dv), p.state) + tuple(p.tokens(w) for w in kept),
        scratch_shapes=[pltpu.VMEM((p.heads, dv, dk), F32), pltpu.VMEM((p.heads, c), F32),
                        pltpu.VMEM((p.heads, c, 2 * c), F32), pltpu.VMEM((p.heads, c, c), F32)],
        name=pk.kernel_name("gdn_fwd", F32, **p.names),
        interpret=interpret,
        compiler_params=p.params(6 + 3 * keep),
    )(*p.consts, q, k, v, g, beta)


def _gdn_bwd(q, k, v, g, beta, st, scores, t_t, uw, do, *, highest: bool, interpret: bool):
    c, dk = q.shape[3:]
    dv = v.shape[-1]
    p = _calls(q, v, reverse=True)
    return pl.pallas_call(
        functools.partial(_gdn_bwd_kernel, highest=highest),
        out_shape=tuple(jax.ShapeDtypeStruct(a.shape, F32) for a in (q, k, v, g, beta)),
        grid=p.grid,
        in_specs=p.const_specs + [p.keys(dk), p.keys(dk), p.tokens(dv), p.rows, p.rows, p.state]
        + [p.tokens(w) for w in _kept(c, dk, dv)] + [p.tokens(dv)],
        out_specs=(p.keys(dk), p.keys(dk), p.tokens(dv), p.rows, p.rows),
        scratch_shapes=[pltpu.VMEM((p.heads, dv, dk), F32), pltpu.VMEM((p.heads, c), F32)],
        name=pk.kernel_name("gdn_bwd", F32, **p.names),
        interpret=interpret,
        compiler_params=p.params(11),
    )(*p.consts, q, k, v, g, beta, st, scores, t_t, uw, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def gdn_chunk_kernels(q, k, v, g, beta, highest: bool, interpret: bool):
    """o [n, r, hv, c, dv] through the kernel pair. A forward that a backward
    follows keeps each chunk's start state, scores, inverse and [U | W] a
    value head for it; the primal forward writes o and the states and
    nothing else, and hands out o alone."""
    return _gdn_fwd(q, k, v, g, beta, keep=False, highest=highest, interpret=interpret)[0]


def _gdn_vjp_fwd(q, k, v, g, beta, highest, interpret):
    o, st, *kept = _gdn_fwd(q, k, v, g, beta, keep=True, highest=highest, interpret=interpret)
    return o, (q, k, v, g, beta, st, *kept)


def _gdn_vjp_bwd(highest, interpret, res, do):
    return _gdn_bwd(*res, do, highest=highest, interpret=interpret)


gdn_chunk_kernels.defvjp(_gdn_vjp_fwd, _gdn_vjp_bwd)
