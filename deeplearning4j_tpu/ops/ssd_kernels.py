"""The state-space rule's chunks — Mamba-2's scalar-decay recurrence in its
chunked "SSD" form — as one Pallas kernel pair (the door the layer calls is
`ops.delta.ssd_chunks`).

The contract is `nn/layers/ssm.py` `ssd_chunked`'s: x [n, r, h, c, p],
dt [n, r, h, c] (after the softplus; 0 on a token that writes nothing and
keeps the state), a [h] (negative), b and c [n, r, g, c, s], float32 and
chunk-major (`to_chunks`) -> (y [n, r, h, c, p] without the skip, the states
the chunks start from [n, r, h, p, s]). Head j reads group j // (h / g). Per
head S_0 = 0 and for every token
S <- exp(dt a) S + dt x (x) B; y = S C.

`dl4j_ssd_fwd_*` walks a group's chunks in order with the states of its
h / g heads in a VMEM scratch; `dl4j_ssd_bwd_*` walks them back with the
states' cotangent there. A chunk's decays, its scores C B^T and the state's
update never leave VMEM, in either direction: the backward forms them again
from x, dt, B, C and the state the chunk started from, which the forward
writes anyway (the layer's counters read it). No `while` over the chunks is
left in the step. The products' helpers are `ops/chunk_kernels.py`'s, shared
with the two delta rules' pairs; this rule has no solve and no inverse.

A program is one (row, group, chunk). The kernels see every array a GROUP at
a time, the tokens on the LANES (the layout XLA gives a 64-wide head anyway:
as [c, 64] every tile would be half padding), a group's heads stacked on the
sublanes: x, y [e p, c] (e = h / g heads of p channels), dt [e, c], B and C
[c, s], the states [e p, s]. Every [c, c] matrix is held TRANSPOSED ([j, i]
for the pair j <= i), as the skeleton holds them. With G the running sum of
dt a inside the chunk:

  G        a ROW a head, all heads of the group in one product
           dt a [e, c] x the triangle of ones, split into three bfloat16
           parts: float32-exact, as `jnp.cumsum` is; the columns the same
           numbers bit for bit (a product with the identity).
  decays   L^T[j, i] = exp(G_i - G_j) for j <= i and 0 elsewhere, the
           exponent masked to <= 0 before the exp, as in the XLA form;
           exp(G_i) and exp(G_last - G_j) a row a head.
  scores   (C B^T)^T = B C^T [c, c] ONCE a group.
  y        a head: (dt x)^T [p, c] x ((C B^T)^T . L^T) + (S C^T) . exp(G);
           S C^T for all heads of the group in ONE product [e p, s] x [s, c].
  state    S <- exp(G_last) S + ((dt x)^T . exp(G_last - G)) B, again one
           product [e p, c] x [c, s] a group.
  back     the same matrices again, then per head the cotangents of (dt x)^T
           and of the masked scores; dB and dC add up over the heads of the
           group inside the program (the scores' part over the heads' sum,
           the state's part as products that contract all e p rows); dG as a
           row a head — the pair terms' column sums less their row sums, the
           read's and the update's terms, the chunk's last token takes what
           exp(G_last) moved — and d(dt a) its running sum run backwards,
           again one exact product for all heads. dt's other cotangent (of
           dt x) leaves as a row a head; a's is finished outside, from
           d(dt a) and dt.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.ops.chunk_kernels import (
    _NN, _NT, _TN, BF16, F32, _dot, _dot_const, _keep, _pairs)

#: tokens a chunk the kernels are written for: one lane tile
CHUNK = 128
#: the most heads a group that a program unrolls
HEADS = 8


def fits(c: int, p: int, s: int, h: int, g: int) -> bool:
    """What the kernels are written for: chunks of one lane tile, a state of
    whole lane tiles, heads of whole sublane tiles of both float32 and the
    packed bfloat16 the products run in, at most `HEADS` heads a group."""
    return c == CHUNK and s % 128 == 0 and p % 16 == 0 and h % g == 0 and h // g <= HEADS


# ---------------------------------------------------------------------------
# one chunk of one group
# ---------------------------------------------------------------------------
def _pair_masks(c: int):
    """(j <= i, j == i) over [c, c], rows j and columns i."""
    j = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    i = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return j <= i, j == i


def _decays(adt, tri, diag, highest: bool):
    """For dt a [e, c] of a group's heads: (G a row a head [e, c], G a column
    a head [c, e], exp(G), exp(G_last - G), exp(G_last) [e, 1], the mask of
    the last token [e, c])."""
    c = adt.shape[1]
    g = _dot_const(tri, adt, _NT, highest, const_first=False)
    gcol = _dot_const(diag.astype(BF16), g, _NT, highest)
    last = lax.broadcasted_iota(jnp.int32, g.shape, 1) == c - 1
    g_last = jnp.sum(_keep(last, g), axis=1, keepdims=True)
    return g, gcol, jnp.exp(g), jnp.exp(g_last - g), jnp.exp(g_last), last


def _decay_t(grow, gcol, lower):
    """L^T [c, c] of a head from its G as a row [1, c] and as a column
    [c, 1]: exp(G_i - G_j) for j <= i, the exponent masked to <= 0 before
    the exp."""
    return _keep(lower, jnp.exp(_keep(lower, grow - gcol)))


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
def _ssd_fwd_kernel(tri_ref, x_ref, dt_ref, adt_ref, b_ref, c_ref, y_ref, s_ref, st_ref, xe_ref,
                    *, highest: bool):
    """One (row, group, chunk) program. The chunks of a group come in order
    and st_ref [e p, s] carries its heads' states; xe_ref [e p, c] gathers
    the heads' decayed writes for the one product that updates them."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    e, c = dt_ref.shape
    p = x_ref.shape[0] // e
    lower, diag = _pair_masks(c)
    g, gcol, since, to_end, decay, _ = _decays(adt_ref[...], tri_ref[...], diag, highest)
    b, cm = b_ref[...], c_ref[...]
    cbt = _dot(b, cm, _NT, highest)                                   # [c_j, c_i]
    s_ref[...] = st_ref[...]
    y_ref[...] = _dot(st_ref[...], cm, _NT, highest)                  # S C^T, all heads
    for j in range(e):
        rows, one = pl.ds(j * p, p), slice(j, j + 1)
        xd = x_ref[rows, :] * dt_ref[pl.ds(j, 1), :]
        wt = cbt * _decay_t(g[one], gcol[:, one], lower)
        y_ref[rows, :] = _dot(xd, wt, _NN, highest) + y_ref[rows, :] * since[one]
        xe_ref[rows, :] = xd * to_end[one]
        st_ref[rows, :] = st_ref[rows, :] * decay[one]
    st_ref[...] += _dot(xe_ref[...], b, _NN, highest)


def _ssd_bwd_kernel(tri_ref, x_ref, dt_ref, adt_ref, b_ref, c_ref, s_ref, dy_ref,
                    dx_ref, ddt_ref, dadt_ref, db_ref, dc_ref, dst_ref, xe_ref, dye_ref, dg_ref,
                    *, highest: bool):
    """The same programs with the chunks of a group in REVERSE order;
    dst_ref [e p, s] carries the cotangent of the states the chunk ends
    with. xe_ref and dye_ref [e p, c] gather the heads' decayed writes and
    decayed cotangents for the products that contract all heads' rows,
    dg_ref [e, c] the heads' dG."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    e, c = dt_ref.shape
    p = x_ref.shape[0] // e
    tri = tri_ref[...]
    lower, diag = _pair_masks(c)
    g, gcol, since, to_end, decay, last = _decays(adt_ref[...], tri, diag, highest)
    b, cm = b_ref[...], c_ref[...]
    cbt = _dot(b, cm, _NT, highest)                                   # [c_j, c_i]
    dx_ref[...] = _dot(dst_ref[...], b, _NT, highest)                 # dS' B^T: of the decayed writes
    xe_ref[...] = _dot(s_ref[...], cm, _NT, highest)                  # S C^T: what the tokens read
    dcbt = jnp.zeros((c, c), F32)
    for j in range(e):
        rows, row, one = pl.ds(j * p, p), pl.ds(j, 1), slice(j, j + 1)
        x, dtr, dy = x_ref[rows, :], dt_ref[row, :], dy_ref[rows, :]
        xd = x * dtr
        lt = _decay_t(g[one], gcol[:, one], lower)
        wt = cbt * lt
        dwt = _dot(xd, dy, _TN, highest)                              # [c_j, c_i]
        dcbt = dcbt + dwt * lt
        # the exponents' cotangent: its column sums are dG_i, its row sums -dG_j
        de = dwt * wt
        to_j = jnp.sum(_keep(diag, jnp.sum(de, axis=1, keepdims=True)), axis=0, keepdims=True)
        dye = dy * since[one]
        dxe = dx_ref[rows, :]
        moved = jnp.sum(dxe * xd, axis=0, keepdims=True) * to_end[one]            # [1, c_j]
        kept = jnp.sum(jnp.sum(dst_ref[rows, :] * s_ref[rows, :], axis=1, keepdims=True),
                       axis=0, keepdims=True)                                     # [1, 1]
        dg_last = jnp.sum(moved, axis=1, keepdims=True) + decay[one] * kept
        dg_ref[row, :] = (jnp.sum(de, axis=0, keepdims=True) - to_j
                          + jnp.sum(dye * xe_ref[rows, :], axis=0, keepdims=True)
                          - moved + _keep(last[one], dg_last))
        dxd = _dot(dy, wt, _NT, highest) + dxe * to_end[one]          # [p, c_j]
        dx_ref[rows, :] = dxd * dtr
        ddt_ref[row, :] = jnp.sum(dxd * x, axis=0, keepdims=True)
        xe_ref[rows, :] = xd * to_end[one]
        dye_ref[rows, :] = dye
    db_ref[...] = _dot(dcbt, cm, _NN, highest) + _dot(xe_ref[...], dst_ref[...], _TN, highest)
    dc_ref[...] = _dot(dcbt, b, _TN, highest) + _dot(dye_ref[...], s_ref[...], _TN, highest)
    for j in range(e):
        rows = pl.ds(j * p, p)
        dst_ref[rows, :] = dst_ref[rows, :] * decay[j:j + 1]
    dst_ref[...] += _dot(dye_ref[...], cm, _NN, highest)
    dadt_ref[...] = _dot_const(tri, dg_ref[...], _NN, highest, const_first=False)  # the sum run backwards


def _plan(x, b, reverse: bool):
    """What both `pallas_call`s share for x [n, r, g, e p, c] and b
    [n, r, g, c, s]: (grid, a block of one group's [rows, columns], the
    triangle of ones with its block, the shape as the kernels' names carry
    it, the compiler's parameters for `arrays` blocks of [e p, c] beside the
    scratches). The chunks innermost — in order, or backwards."""
    n, r, g, ep, c = x.shape
    s = b.shape[-1]

    def group(rows, cols):
        return pl.BlockSpec((None, None, None, rows, cols),
                            lambda ri, gi, ni: ((n - 1 - ni if reverse else ni), ri, gi, 0, 0))

    tri = jnp.asarray(_pairs(c)[0], BF16)

    def params(arrays: int):
        # the blocks double-buffered, the scratches, and room for two dozen [c, c] values
        need = 4 * ep * (2 * arrays * max(c, s) + 3 * c + s) + 4 * 24 * c * max(c, s)
        return pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=min(max(2 * need, 32 * 2 ** 20), 100 * 2 ** 20))

    return (r, g, n), group, tri, pl.BlockSpec(tri.shape, lambda *i: (0, 0)), params


def _names(x, dt, b):
    """The shape in a kernel's name, the chunk count first (the benchmark's
    trace reader folds a name whose first dimension is `n<digits>` into its
    family): `n64_r1_h64g8_c128_p64s128`."""
    n, r, g, ep, c = x.shape
    h = g * dt.shape[3]
    return dict(n=n, r=r, h=f"{h}g{g}", c=c, p=f"{ep * g // h}s{b.shape[-1]}")


def _ssd_fwd(x, dt, adt, b, c, *, highest: bool, interpret: bool):
    ep, cl = x.shape[3:]
    e, s = dt.shape[3], b.shape[-1]
    grid, group, tri, tri_spec, params = _plan(x, b, reverse=False)
    return pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, highest=highest),
        out_shape=(jax.ShapeDtypeStruct(x.shape, F32),
                   jax.ShapeDtypeStruct(x.shape[:3] + (ep, s), F32)),
        grid=grid,
        in_specs=[tri_spec, group(ep, cl), group(e, cl), group(e, cl), group(cl, s), group(cl, s)],
        out_specs=(group(ep, cl), group(ep, s)),
        scratch_shapes=[pltpu.VMEM((ep, s), F32), pltpu.VMEM((ep, cl), F32)],
        name=pk.kernel_name("ssd_fwd", F32, **_names(x, dt, b)),
        interpret=interpret,
        compiler_params=params(3),
    )(tri, x, dt, adt, b, c)


def _ssd_bwd(x, dt, adt, b, c, st, dy, *, highest: bool, interpret: bool):
    ep, cl = x.shape[3:]
    e, s = dt.shape[3], b.shape[-1]
    grid, group, tri, tri_spec, params = _plan(x, b, reverse=True)
    return pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, highest=highest),
        out_shape=tuple(jax.ShapeDtypeStruct(a.shape, F32) for a in (x, dt, adt, b, c)),
        grid=grid,
        in_specs=[tri_spec, group(ep, cl), group(e, cl), group(e, cl), group(cl, s), group(cl, s),
                  group(ep, s), group(ep, cl)],
        out_specs=(group(ep, cl), group(e, cl), group(e, cl), group(cl, s), group(cl, s)),
        scratch_shapes=[pltpu.VMEM((ep, s), F32), pltpu.VMEM((ep, cl), F32),
                        pltpu.VMEM((ep, cl), F32), pltpu.VMEM((e, cl), F32)],
        name=pk.kernel_name("ssd_bwd", F32, **_names(x, dt, b)),
        interpret=interpret,
        compiler_params=params(4),
    )(tri, x, dt, adt, b, c, st, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernels(x, dt, adt, b, c, highest: bool, interpret: bool):
    return _ssd_fwd(x, dt, adt, b, c, highest=highest, interpret=interpret)


def _vjp_fwd(x, dt, adt, b, c, highest, interpret):
    y, st = _ssd_fwd(x, dt, adt, b, c, highest=highest, interpret=interpret)
    return (y, st), (x, dt, adt, b, c, st)


def _vjp_bwd(highest, interpret, res, cts):
    return _ssd_bwd(*res, cts[0], highest=highest, interpret=interpret)   # none flows through the states


_kernels.defvjp(_vjp_fwd, _vjp_bwd)


def ssd_chunk_kernels(x, dt, a, b, c, highest: bool, interpret: bool):
    """(y [n, r, h, c, p], the states the chunks start from [n, r, h, p, s])
    through the kernel pair. The kernels see a group's heads stacked and
    every chunk of x and y transposed: a change of the arrays' layout for XLA
    to settle with their producers, no pass. The backward needs nothing the
    forward does not hand out; no cotangent flows through the states."""
    n, r, h, cl, p = x.shape
    g, s = b.shape[2], b.shape[-1]
    e = h // g
    dt = dt.reshape(n, r, g, e, cl)
    y, st = _kernels(x.swapaxes(-1, -2).reshape(n, r, g, e * p, cl), dt, dt * a.reshape(g, e, 1),
                     b, c, highest, interpret)
    return y.reshape(n, r, h, p, cl).swapaxes(-1, -2), st.reshape(n, r, h, p, s)
