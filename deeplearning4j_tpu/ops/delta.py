"""The door to the recurrent mixers' kernels — the delta rules', the
state-space rule's, their short convolution's: what a layer calls between its
projections, and the rule that says where a kernel runs (one entry a kernel
family; a rule on what the call site can see, and nothing else)."""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.ops import chunk_kernels
from deeplearning4j_tpu.ops import convsilu_kernels
from deeplearning4j_tpu.ops import gdn_kernels
from deeplearning4j_tpu.ops import kda_kernels
from deeplearning4j_tpu.ops import kernel_call
from deeplearning4j_tpu.ops import linear
from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.ops import ssd_kernels


def kda_impl(impl: str, q, v) -> str:
    """'pallas' | 'xla' for the per-channel delta rule over chunk-major
    q [n, r, h, c, dk] and v [.., dv]: the kernels take float32 arrays in
    chunks of `chunk_kernels.CHUNK` tokens whose keys and values are one
    width of whole lanes (a multiple of 128). 'auto' wants a TPU backend
    with the helpers on and rows that split evenly over an ambient data
    mesh; an explicit 'pallas' skips those two gates (the CPU tests run the
    kernels interpreted; under a mesh no kernel can follow it raises in
    `per_batch_shard`)."""
    n, r, h, c, dk = q.shape
    fits = (q.dtype == v.dtype == jnp.float32 and c == chunk_kernels.CHUNK
            and dk == v.shape[-1] and dk % 128 == 0)
    return pk.which(impl, fits, r)


def kda_chunks(q, k, v, g, beta, impl: str = "auto"):
    """The per-channel delta rule over chunk-major q, k, g [n, r, h, c, dk],
    v [.., dv], beta [n, r, h, c] through the kernel pair `dl4j_kda_fwd` /
    `dl4j_kda_bwd`: (o [n, r, h, c, dv], the states the chunks start from
    [n, r, h, dk, dv]; no cotangent flows through the states) — or None
    where `kda_impl` declines and the caller keeps its XLA form. Products
    run at `linear._precision()`. Under a data mesh each device runs its
    own rows."""
    if kda_impl(impl, q, v) != "pallas":
        return None
    highest = linear._precision() is not None
    interpret = kernel_call.interpret()

    def rows_first(*a):     # the shard mapping splits axis 0: rows in front, and back
        o, st = kda_kernels.kda_chunk_kernels(*(x.swapaxes(0, 1) for x in a), highest, interpret)
        return o.swapaxes(0, 1), st.swapaxes(0, 1)

    o, st = kernel_call.per_batch_shard(
        rows_first, tuple(x.swapaxes(0, 1) for x in (q, k, v, g, beta)), (True,) * 5)
    return o.swapaxes(0, 1), lax.stop_gradient(st.swapaxes(0, 1).swapaxes(-1, -2))


def gdn_impl(impl: str, q, v) -> str:
    """'pallas' | 'xla' for the gated delta rule (one decay a head and token)
    over chunk-major q [n, r, hk, c, dk] and v [n, r, hv, c, dv]: the kernels
    take float32 arrays in chunks of `chunk_kernels.CHUNK` tokens whose keys
    and values are one width of whole lanes (a multiple of 128), hk dividing
    hv (a program takes all heads where its `_HEADS` is no multiple of
    hv / hk). 'auto' and an explicit 'pallas' as for `kda_impl`."""
    n, r, hk, c, dk = q.shape
    fits = (q.dtype == v.dtype == jnp.float32 and c == chunk_kernels.CHUNK
            and dk == v.shape[-1] and dk % 128 == 0 and v.shape[2] % hk == 0)
    return pk.which(impl, fits, r)


def gdn_chunks(q, k, v, g, beta, impl: str = "auto"):
    """The gated delta rule over chunk-major q, k [n, r, hk, c, dk],
    v [n, r, hv, c, dv], g and beta [n, r, hv, c] through the kernel pair
    `dl4j_gdn_fwd` / `dl4j_gdn_bwd`: o [n, r, hv, c, dv] — or None where
    `gdn_impl` declines and the caller keeps its XLA form
    (`hybrid.chunk_gated_delta_rule`). Products run at
    `linear._precision()`. Under a data mesh each device runs its own rows."""
    if gdn_impl(impl, q, v) != "pallas":
        return None
    highest = linear._precision() is not None
    interpret = kernel_call.interpret()

    def rows_first(*a):     # the shard mapping splits axis 0: rows in front, and back
        return gdn_kernels.gdn_chunk_kernels(
            *(x.swapaxes(0, 1) for x in a), highest, interpret).swapaxes(0, 1)

    return kernel_call.per_batch_shard(
        rows_first, tuple(x.swapaxes(0, 1) for x in (q, k, v, g, beta)), (True,) * 5).swapaxes(0, 1)


def ssd_impl(impl: str, x, b) -> str:
    """'pallas' | 'xla' for the state-space rule over chunk-major
    x [n, r, h, c, p] and b [n, r, g, c, s]: the kernels take float32 arrays
    in chunks of `ssd_kernels.CHUNK` tokens (one lane tile), a state of whole
    lane tiles (s a multiple of 128), heads of a multiple of 16 channels, g
    dividing h with at most `ssd_kernels.HEADS` heads a group
    (`ssd_kernels.fits`). 'auto' and an explicit 'pallas' as for `kda_impl`."""
    n, r, h, c, p = x.shape
    fits = (x.dtype == b.dtype == jnp.float32
            and ssd_kernels.fits(c, p, b.shape[-1], h, b.shape[2]))
    return pk.which(impl, fits, r)


def ssd_chunks(x, dt, a, b, c, impl: str = "auto"):
    """The state-space rule over chunk-major x [n, r, h, c, p], dt
    [n, r, h, c], a [h], b and c [n, r, g, c, s] through the kernel pair
    `dl4j_ssd_fwd` / `dl4j_ssd_bwd`: (y [n, r, h, c, p] without the skip,
    the states the chunks start from [n, r, h, p, s]; no cotangent flows
    through the states) — or None where `ssd_impl` declines and the caller
    keeps its XLA form (`ssm.ssd_chunked`). Products run at
    `linear._precision()`. Under a data mesh each device runs its own rows, a
    arrives whole and its cotangent is summed over the devices."""
    if ssd_impl(impl, x, b) != "pallas":
        return None
    highest = linear._precision() is not None
    interpret = kernel_call.interpret()

    def rows_first(a_, *rows):    # the shard mapping splits axis 0: rows in front, and back
        x_, dt_, b_, c_ = (t.swapaxes(0, 1) for t in rows)
        y, st = ssd_kernels.ssd_chunk_kernels(x_, dt_, a_, b_, c_, highest, interpret)
        return y.swapaxes(0, 1), st.swapaxes(0, 1)

    y, st = kernel_call.per_batch_shard(
        rows_first, (a,) + tuple(t.swapaxes(0, 1) for t in (x, dt, b, c)), (False,) + (True,) * 4)
    return y.swapaxes(0, 1), lax.stop_gradient(st.swapaxes(0, 1))


def conv_silu_impl(impl: str, x, w) -> str:
    """'pallas' | 'xla' for the short causal convolution + silu over
    chunk-major x [n, r, h, c, d] with taps w [cw, h, 1, d]: the kernels take
    bfloat16 or float32 rows and at most 9 taps, d whole lane tiles (a
    multiple of 128) over chunks of a multiple of 16 tokens, or a head
    narrower than 128 (a multiple of 16) over chunks of exactly 128
    (`convsilu_kernels.fits`). 'auto' and an explicit 'pallas' as for
    `kda_impl`."""
    n, r, h, c, d = x.shape
    fits = convsilu_kernels.fits(c, d, w.shape[0], x.dtype)
    return pk.which(impl, fits, r)


def conv_silu_chunks(x, w, b=None, impl: str = "auto"):
    """silu(the short causal depthwise convolution of chunk-major x
    [n, r, h, c, d] with w [cw, h, 1, d] + b [h, 1, d]) float32 through the
    kernel pair `dl4j_convsilu_fwd` / `dl4j_convsilu_bwd` — or None where
    `conv_silu_impl` declines and the caller keeps its XLA form
    (`hybrid._conv_silu`). Under a data mesh each device runs its own rows,
    w and b arrive whole and their cotangents are summed over the devices."""
    if conv_silu_impl(impl, x, w) != "pallas":
        return None
    interpret = kernel_call.interpret()

    def rows_first(x_, w_, *b_):    # the shard mapping splits axis 0: rows in front, and back
        return convsilu_kernels.conv_silu_kernels(
            x_.swapaxes(0, 1), w_, b_[0] if b_ else None, interpret).swapaxes(0, 1)

    args = (x.swapaxes(0, 1), w) + (() if b is None else (b,))
    return kernel_call.per_batch_shard(
        rows_first, args, (True,) + (False,) * (len(args) - 1)).swapaxes(0, 1)
