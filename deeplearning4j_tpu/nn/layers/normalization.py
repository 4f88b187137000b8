"""Normalization layers: BatchNorm, LocalResponseNormalization.

Reference: nn/conf/layers/BatchNormalization.java + runtime
nn/layers/normalization/BatchNormalization.java (cuDNN path
CudnnBatchNormalizationHelper.java:234), LocalResponseNormalization.java
(CudnnLocalResponseNormalizationHelper.java:211).

TPU-native: the whole BN math is a handful of elementwise+reduce ops XLA
fuses into neighbors; NHWC layout makes the normalized axis the last one for
both FF [b, f] and CNN [b, h, w, c] inputs. Running stats are STATE (the
functional-core analogue of DL4J's mutable globalMean/globalVar params).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp

from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.ops import fused_affine_act


@register_layer
@dataclass
class BatchNorm(Layer):
    """gamma/beta trained; running mean/var tracked by EMA with `decay`
    (DL4J default decay=0.9, eps=1e-5; lockGammaBeta freezes scale/shift)."""

    decay: float = 0.9
    eps: float = 1e-5
    lock_gamma_beta: bool = False
    gamma_init: float = 1.0
    beta_init: float = 0.0

    def _nf(self, input_type):
        if isinstance(input_type, it.Convolutional):
            return input_type.channels
        if isinstance(input_type, it.Recurrent):
            return input_type.size
        return input_type.arity()

    def output_type(self, input_type):
        return input_type

    def init_params(self, rng, input_type):
        n = self._nf(input_type)
        if self.lock_gamma_beta:
            return {}
        return {
            "gamma": jnp.full((n,), self.gamma_init, jnp.float32),
            "beta": jnp.full((n,), self.beta_init, jnp.float32),
        }

    def init_state(self, input_type):
        n = self._nf(input_type)
        return {
            "mean": jnp.zeros((n,), jnp.float32),
            "var": jnp.ones((n,), jnp.float32),
        }

    def regularizable(self, params):
        return {}

    def apply(self, params, x, *, state, train, rng, mask=None):
        axes = tuple(range(x.ndim - 1))  # all but channel/feature
        if train:
            # Stats with f32 accumulation (dtype=f32 folds the upcast into
            # the reduction — bf16 stats would lose too many mantissa
            # bits; f64 gradient-check runs keep their precision via
            # x.dtype >= f32). The stable two-reduce E[(x-mean)^2] form is
            # used rather than one-pass E[x^2]-E[x]^2: the latter cancels
            # catastrophically in f32 when |mean| >> std (e.g. BN over
            # unnormalized pixel-scale activations), and on TPU the two
            # fused reduces measure within noise of the one-pass version.
            acc = jnp.float32 if x.dtype == jnp.bfloat16 else x.dtype
            mean = jnp.mean(x, axis=axes, dtype=acc)
            var = jnp.mean(jnp.square(x.astype(acc) - mean), axis=axes)
            d = self.decay
            new_state = {
                "mean": d * state["mean"] + (1 - d) * mean,
                "var": d * state["var"] + (1 - d) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        inv = 1.0 / jnp.sqrt(var + self.eps)
        scale, shift = inv, -mean * inv
        if not self.lock_gamma_beta:
            scale = scale * params["gamma"]
            shift = shift * params["gamma"] + params["beta"]
        y = self._affine_act(x, scale, shift)
        return y, new_state

    def _affine_act(self, x, scale, shift):
        """The memory-bound epilogue y = act(x*scale + shift): the fused
        pallas conv-bn-relu epilogue where `ops.fused_affine_act` admits
        it (opt-in, DL4J_TPU_PALLAS_CONVBN=1), else XLA, which fuses it
        into the producing conv."""
        act = self.activation if self.activation is not None else "identity"
        y = fused_affine_act(x, scale, shift, act)
        if y is not None:
            return y
        y = x * scale.astype(x.dtype) + shift.astype(x.dtype)
        return self.act_fn("identity")(y)


@register_layer
@dataclass
class LRN(Layer):
    """Local response normalization across channels
    (nn/conf/layers/LocalResponseNormalization.java; DL4J defaults k=2, n=5,
    alpha=1e-4, beta=0.75)."""

    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75

    def has_params(self):
        return False

    def output_type(self, input_type):
        return input_type

    def apply(self, params, x, *, state, train, rng, mask=None):
        half = int(self.n) // 2
        sq = x * x
        # sum over a window of `n` adjacent channels (last axis, NHWC)
        c = x.shape[-1]
        padded = jnp.pad(sq, [(0, 0)] * (x.ndim - 1) + [(half, half)])
        acc = jnp.zeros_like(x)
        for i in range(int(self.n)):
            acc = acc + padded[..., i : i + c]
        denom = (self.k + self.alpha * acc) ** self.beta
        return x / denom, state
