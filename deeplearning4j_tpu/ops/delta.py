"""The door to the delta rules' kernels: what a layer calls between its
projections, and the rule that says where a kernel runs (one entry a kernel
family; a rule on what the call site can see, and nothing else)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.ops import chunk_kernels
from deeplearning4j_tpu.ops import gdn_kernels
from deeplearning4j_tpu.ops import kda_kernels
from deeplearning4j_tpu.ops import kernel_call
from deeplearning4j_tpu.ops import linear
from deeplearning4j_tpu.ops import pallas_kernels as pk


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
    if impl == "auto":
        fits = (fits and pk.helpers_enabled() and jax.default_backend() == "tpu"
                and bool(kernel_call.per_device_batch(r)))
    return "pallas" if fits and impl in ("auto", "pallas") else "xla"


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
    if impl == "auto":
        fits = (fits and pk.helpers_enabled() and jax.default_backend() == "tpu"
                and bool(kernel_call.per_device_batch(r)))
    return "pallas" if fits and impl in ("auto", "pallas") else "xla"


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
