"""MXU-targeted matmul / conv primitives.

These are the framework's equivalents of ND4J `gemm` / cuDNN
`cudnnConvolutionForward` (deeplearning4j-cuda CudnnConvolutionHelper.java:480).

Precision policy: arrays stay float32; XLA:TPU's DEFAULT dot/conv precision
executes f32 contractions as bfloat16 MXU passes with f32 accumulation —
exactly the bf16-compute/f32-accumulate policy we want, with exact f32 on CPU
(where gradient checks run). `dtypes.full_precision()` bumps to HIGHEST
(three-pass bf16) for numerics-sensitive paths on TPU.

XLA fuses the surrounding elementwise ops (bias add, activation) into the
matmul/conv — no hand-written fusion needed (SURVEY.md §7).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu import dtypes

# NHWC activations, HWIO kernels — XLA:TPU preferred conv layout.
CONV_DIMS = ("NHWC", "HWIO", "NHWC")


def _precision():
    return lax.Precision.HIGHEST if dtypes.matmul_precision_dtype() is None else None


def _mixed_cast(x, w):
    """bf16 operands under the mixed-precision policy (bf16 activations out,
    f32 MXU accumulation happens regardless of output dtype)."""
    if dtypes.mixed_precision() and x.dtype in (jnp.float32, jnp.bfloat16):
        bf = jnp.bfloat16
        return x.astype(bf), w.astype(bf)
    return x, w


def bias_add(z: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """z + b in z's dtype. Under the mixed policy z is bf16 while params are
    f32; a plain `z + b` would silently promote activations back to f32 and
    forfeit the halved HBM traffic."""
    return z + b.astype(z.dtype)


def dot(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """x @ w on the MXU (bf16 compute / f32 accumulate on TPU)."""
    x, w = _mixed_cast(x, w)
    return jnp.matmul(x, w, precision=_precision())


def dot_general(x, w, dims, **kw):
    x, w = _mixed_cast(x, w)
    return lax.dot_general(x, w, dims, precision=_precision(), **kw)


#: the largest tile libtpu gives a width of its grouped product
GROUPED_TILE = 512


def grouped_width(n: int) -> int:
    """The width at which `grouped_dot` runs an operand or a result of width n:
    the next multiple of 512 where that adds at most a quarter, else n itself.
    libtpu tiles each width of its grouped product by the largest of 512, 256
    and 128 that divides it, and the tile sets the rate: on a v5e 133-148
    TFLOP/s at 512 x 512 (3072 x 2048), 96-127 at 256 x 512, 25-33 at
    128 x 128 (2688 x 1856, or 2688 x 1920) — PERF.md section 6, PR 32."""
    padded = -(-n // GROUPED_TILE) * GROUPED_TILE
    return padded if 4 * (padded - n) <= n else n


def grouped_dot(x: jnp.ndarray, w: jnp.ndarray, sizes: jnp.ndarray, blocks: int = 1) -> jnp.ndarray:
    """Rows of x [rows, K], sorted by group (`sizes` rows each), times their
    group's matrix w [g, k, blocks * n] -> [rows, blocks * grouped_width(n)]:
    `lax.ragged_dot` (XLA's grouped product) on widths it runs well.

    Padding is with zeros and is exact: x may already be wider than k (its
    columns beyond k must be zero; they meet zero rows of w), and each of the
    `blocks` column blocks of w is padded to `grouped_width(n)`, so the result
    has zero columns there. They stay: the next product's matrix gets zero
    rows for them, and whoever reduces the rows to tokens slices once, there.
    Only the matrices are padded here, after the cast, never the rows. At
    widths that are their own `grouped_width` this is `lax.ragged_dot` on the
    operands as they came."""
    x, w = _mixed_cast(x, w)
    g, k, n = w.shape[0], w.shape[1], w.shape[2] // blocks
    wide, n_pad = x.shape[-1], grouped_width(n)
    if (wide, n_pad) != (k, n):
        w = jnp.pad(w.reshape(g, k, blocks, n),
                    ((0, 0), (0, wide - k), (0, 0), (0, n_pad - n))).reshape(g, wide, blocks * n_pad)
    return lax.ragged_dot(x, w, sizes, precision=_precision())


def conv2d(
    x: jnp.ndarray,
    kernel: jnp.ndarray,
    stride: Tuple[int, int],
    padding,
    dilation: Tuple[int, int] = (1, 1),
    feature_group_count: int = 1,
) -> jnp.ndarray:
    """NHWC conv. `padding` is 'SAME', 'VALID', or [(ph,ph),(pw,pw)]."""
    x, kernel = _mixed_cast(x, kernel)
    return lax.conv_general_dilated(
        x,
        kernel,
        window_strides=stride,
        padding=padding,
        rhs_dilation=dilation,
        dimension_numbers=CONV_DIMS,
        feature_group_count=feature_group_count,
        precision=_precision(),
    )


def conv2d_transpose(
    x: jnp.ndarray,
    kernel: jnp.ndarray,
    stride: Tuple[int, int],
    padding,
) -> jnp.ndarray:
    """NHWC transposed conv (Deconvolution2D)."""
    x, kernel = _mixed_cast(x, kernel)
    return lax.conv_transpose(
        x,
        kernel,
        strides=stride,
        padding=padding,
        dimension_numbers=CONV_DIMS,
        precision=_precision(),
    )
