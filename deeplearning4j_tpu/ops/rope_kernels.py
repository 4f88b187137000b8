"""Rotary positions and the split into heads as one Pallas pass over a
projection's columns (the door the attention layers call is
`ops.attention.rope_heads`).

The contract is `nn/layers/hybrid.py` `rotary`'s at the half-split pairing
from feature 0, with the head split in front of it: a [b, t, N d] (a
projection's output, or a part of it behind a norm: N heads of d columns
side by side; bfloat16 or float32) and a list of parts — `heads[p]` heads
each, in column order, `turned[p]` says whether the part's heads get
positions — -> one array [b, heads[p], t, d] a part, in a's dtype. A turned
head's first `rot` features turn by pos theta^(-2j / rot), feature j paired
with j + rot/2, float32 inside and ONE rounding at the end; everything else
is copied.

It is a function of bytes alone, and XLA makes many passes over them: the
transpose to heads, a float32 copy, the slices of the two halves, four
multiplies, the concatenate (11.5 x the bytes of q and k as compiled for a
v5e, PERF.md section 6, PR 46). Here

`dl4j_rope_fwd_*`  a program takes `tb` tokens of ALL columns [tb, N d] and
    the tables' rows [tb, lanes]; head i is the lane-aligned column window
    i d .. (i + 1) d, so the head split is the index of the block it is
    written to, [n, tb, d] of its part. y = x cos + partner(x) sin with the
    sign of the pairing folded into the sin table; partner is ONE lane
    rotation by rot/2 where the part is a whole lane tile, two and a select
    where it is less, a swap of lane tiles where half of it is whole tiles —
    exact in any dtype. Read once, written once.
`dl4j_rope_bwd_*`  the same program the other way round with sin negated (a
    rotation is orthogonal: da = rot(-angle)(dy)): reads the parts'
    cotangents [n, tb, d], writes the projection's column layout [tb, N d]
    WHOLE — which is why the parts cover every column: a `custom_vjp`'s
    cotangent has its input's shape, so a pass over some columns would leave
    XLA to pad and add the others in. No residual: the tables are functions of
    static numbers.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import pallas_kernels as pk

F32 = jnp.float32

#: elements of a a program takes
_BLOCK = 2 ** 20
#: tokens a block is a multiple of: a whole tile of either dtype
_ROWS = 16


def _lanes(d: int, rot: int) -> int:
    """Columns of a head the tables cover: the lane tiles the turned part
    lies in (the rest of the head is copied)."""
    return min(d, -(-rot // 128) * 128)


def fits(t: int, d: int, rot: int, dtype) -> bool:
    """What the kernels are written for: bfloat16 or float32; heads of whole
    lane tiles; whole tiles of tokens; a turned part that lies in ONE lane
    tile, or that is whole lane tiles whose half is too."""
    lanes = _lanes(d, rot)
    return (jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(F32))
            and d % 128 == 0 and t % _ROWS == 0 and 0 < rot <= d and rot % 2 == 0
            and (lanes == 128 or (rot == lanes and rot % 256 == 0)))


def frequencies(rot: int, theta: float, scaling=None, j=None):
    """(f_j, scale): the angle a position turns pair j of `rot` rotary
    features by, float32, and what cos and sin are multiplied by — the ONE
    place a frequency schedule is computed (`hybrid.rotary` and `tables`
    both call it). `j`: the pair indices as a float32 array (default 0 ..
    rot/2 - 1). `scaling`: a published `rope_parameters` entry as it stands
    (a dict, or its items); None, or `rope_type` "default": f_j =
    theta^(-2j/rot), scale 1.0. "yarn": with e_j = theta^(-2j/rot), c(n) =
    rot ln(original_max_position_embeddings / (2 pi n)) / (2 ln theta), lo =
    floor(c(beta_fast)), hi = ceil(c(beta_slow)) (both kept inside 0 ..
    rot - 1), r_j = clip((j - lo) / (hi - lo), 0, 1): f_j = e_j (1 - r_j) +
    (e_j / factor) r_j — the fast pairs turn as they were trained, the slow
    ones `factor` times slower — and scale = `attention_factor` (default
    0.1 ln(factor) + 1)."""
    if j is None:
        j = jnp.arange(rot // 2, dtype=F32)
    plain = theta ** (-2.0 * j / rot)
    scaling = dict(scaling or {})
    kind = scaling.get("rope_type", "default")
    if kind == "default":
        return plain, 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type={kind!r}: 'default' or 'yarn'")
    factor = float(scaling["factor"])
    span = float(scaling["original_max_position_embeddings"])

    def turns_at(n):   # the pair that turns n times over the original span
        return rot * math.log(span / (n * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(turns_at(float(scaling.get("beta_fast", 32)))), 0)
    hi = min(math.ceil(turns_at(float(scaling.get("beta_slow", 1)))), rot - 1)
    ramp = jnp.clip((j - lo) / (hi - lo if hi > lo else 0.001), 0.0, 1.0)
    scale = scaling.get("attention_factor")
    return (plain * (1.0 - ramp) + plain / factor * ramp,
            float(0.1 * math.log(factor) + 1.0 if scale is None else scale))


def tables(t: int, d: int, rot: int, theta: float, scaling=None):
    """(cos, sin) [t, lanes] float32 such that a head's first `lanes`
    features turn as y = x cos + partner(x) sin: `rotary`'s angles, the
    pairing's sign in sin (feature j < rot/2 takes -sin of its partner
    j + rot/2, which takes +sin of it), cos 1 and sin 0 beyond `rot`; with a
    `scaling` the schedule's frequencies and its factor on both tables
    (`frequencies`)."""
    half, rest = rot // 2, _lanes(d, rot) - rot
    j = jnp.arange(half, dtype=F32)
    pos = jnp.arange(t, dtype=F32)[:, None]
    freq, scale = frequencies(rot, theta, scaling, j)
    ang = pos * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    return (jnp.concatenate([cos, cos, jnp.ones((t, rest), F32)], axis=1),
            jnp.concatenate([-sin, sin, jnp.zeros((t, rest), F32)], axis=1))


def _partner(x, rot: int):
    """x [rows, lanes] float32 with feature j +- rot/2 in feature j's place,
    j < rot (what stands beyond `rot` meets a sin of 0)."""
    lanes, half = x.shape[1], rot // 2
    if half % 128 == 0:                                  # whole lane tiles change places
        return jnp.concatenate([x[:, half:], x[:, :half]], axis=1)
    back = pltpu.roll(x, jnp.int32(half), 1)             # feature j - half
    if rot == lanes:
        return back                                      # = j + half: the tile is the part
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane < half, pltpu.roll(x, jnp.int32(lanes - half), 1), back)


def _kernel(*refs, heads, turned, d: int, rot: int, back: bool):
    """Forward: a [tb, N d], cos, sin [tb, lanes] -> a part's [n, tb, d] each.
    `back`: the parts' [n, tb, d], cos, sin -> a [tb, N d], sin negated. ONE
    loop whose trip i does head i of every part (a part with fewer heads sits
    the later trips out): a body unrolled over Ouro's 48 heads costs 80 ms a
    call site to trace and lower."""
    if back:
        *parts, cos_ref, sin_ref, a_ref = refs
    else:
        a_ref, cos_ref, sin_ref, *parts = refs
    lanes = cos_ref.shape[1]

    def head(part_ref, first, turn, i):
        col = pl.multiple_of((first + i) * d, 128)           # the head's window of the columns

        def window(lo, hi):                                  # (what is written, what is read)
            part, cols = part_ref.at[i, :, lo:hi], a_ref.at[:, pl.ds(col + lo, hi - lo)]
            return (cols, part) if back else (part, cols)

        if turn:
            dst, src = window(0, lanes)
            x = src[...].astype(F32)
            swing = _partner(x, rot) * sin_ref[...]
            y = x * cos_ref[...]
            dst[...] = (y - swing if back else y + swing).astype(dst.dtype)
        rest = lanes if turn else 0
        if rest < d:
            dst, src = window(rest, d)
            dst[...] = src[...]

    def trip(i, c):
        first = 0
        for part_ref, n, turn in zip(parts, heads, turned):
            if n == max(heads):
                head(part_ref, first, turn, i)
            else:
                pl.when(i < n)(functools.partial(head, part_ref, first, turn, i))
            first += n
        return c

    lax.fori_loop(jnp.int32(0), jnp.int32(max(heads)), trip, jnp.int32(0))


def _tokens(t: int, width: int) -> int:
    """Tokens a program: the largest divisor of t in whole tiles that keeps
    its [tokens, width] block within `_BLOCK` elements. Every plan of 2^19
    elements and more moves Ouro's columns at the same 560 - 580 GB/s, a head
    a program needs 2048 tokens for it (PERF.md section 6, PR 46)."""
    return max(k for k in range(_ROWS, t + 1, _ROWS)
               if t % k == 0 and (k * width <= _BLOCK or k == _ROWS))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7, 8))
def _call(operands, heads, turned, d: int, rot: int, theta: float, back: bool, interpret: bool,
          scaling=None):
    """One direction's `pallas_call` with its tables: a [b, t, N d] (`back`:
    the parts' cotangents) -> the parts (a's cotangent). A jitted function of
    its own, so that the call sites of a step with the same shapes — 72 in
    Ouro's — are traced and lowered ONCE a direction and called from there
    (XLA inlines the calls and prefixes each site's own name stack: a site's
    scope still names its kernel in the trace). Traced a site, the pair costs
    a step of 24 layer applications 2 - 8 s of `setup_s`."""
    shape, dtype = operands[0].shape, operands[0].dtype
    b, t = (shape[0], shape[2]) if back else shape[:2]
    width = sum(heads) * d
    lanes = _lanes(d, rot)
    tb = _tokens(t, width)
    cols = pl.BlockSpec((None, tb, width), lambda bi, ti: (bi, ti, 0))
    table = pl.BlockSpec((tb, lanes), lambda bi, ti: (ti, 0))
    split = [pl.BlockSpec((None, n, tb, d), lambda bi, ti: (bi, 0, ti, 0)) for n in heads]
    whole = jax.ShapeDtypeStruct((b, t, width), dtype)
    parts = tuple(jax.ShapeDtypeStruct((b, n, t, d), dtype) for n in heads)
    need = 2 * (2 * tb * width * jnp.dtype(dtype).itemsize + 2 * tb * lanes * 4)
    return pl.pallas_call(
        functools.partial(_kernel, heads=heads, turned=turned, d=d, rot=rot, back=back),
        out_shape=whole if back else parts,
        grid=(b, t // tb),
        in_specs=(split if back else [cols]) + [table, table],
        out_specs=cols if back else split,
        name=pk.kernel_name("rope_bwd" if back else "rope_fwd", dtype,
                            bh=b * sum(heads), t=t, d=d, r=rot),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=min(max(need + 2 ** 23, 32 * 2 ** 20), 100 * 2 ** 20)),
    )(*operands, *tables(t, d, rot, theta, scaling))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6, 7))
def rope_split_kernels(a, heads, turned, d: int, rot: int, theta: float, interpret: bool,
                       scaling: Optional[tuple] = None):
    """a [b, t, sum(heads) d] -> one [b, heads[p], t, d] a part, the heads of
    a part with `turned[p]` rotated (the module docstring), through the
    kernel pair `dl4j_rope_fwd` / `dl4j_rope_bwd`. `scaling`: a frequency
    schedule's items as a sorted tuple (hashable: it is a static argument),
    or None."""
    return _call((a,), heads, turned, d, rot, theta, False, interpret, scaling)


def _vjp_fwd(a, heads, turned, d, rot, theta, interpret, scaling=None):
    return _call((a,), heads, turned, d, rot, theta, False, interpret, scaling), None


def _vjp_bwd(heads, turned, d, rot, theta, interpret, scaling, _, dys):
    return (_call(tuple(dys), heads, turned, d, rot, theta, True, interpret, scaling),)


rope_split_kernels.defvjp(_vjp_fwd, _vjp_bwd)
