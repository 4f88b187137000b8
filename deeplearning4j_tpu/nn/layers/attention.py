"""Attention / transformer layers — net-new TPU-first capability.

The reference (2017-era DL4J) predates transformers entirely: SURVEY.md §5
records "no ring attention, no Ulysses, no context parallel, no attention at
all". These layers are the north-star-mandated extension of the layer
library, built on the same Layer protocol as the 41 reference-parity configs
so they compose with MultiLayerNetwork / ComputationGraph, masking, tBPTT-era
iterators and the zoo.

Layers (all BTF [batch, time, features], the framework RNN layout):
  LayerNorm            — per-feature normalization (transformer workhorse).
  PositionEmbedding    — learned or fixed sinusoidal position encodings.
  MultiHeadAttention   — self-attention; causal option; key-padding masks
                         follow the [b, t] RNN mask convention. When a
                         `ops.ring.sequence_parallel(axis)` context is
                         active during tracing, dispatches to ring attention
                         over the mesh axis (exact long-context attention,
                         K/V rotated over ICI).
  TransformerBlock     — pre-LN encoder/decoder-style block:
                         x += MHA(LN(x)); x += FFN(LN(x)).

Weight layouts are gemm-friendly [n_in, n_out] like Dense (DL4J convention);
q/k/v projections are fused into one [f, 3f] matmul for MXU efficiency.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import initializers as init_mod
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.layers.base import Layer, apply_dropout, register_layer
from deeplearning4j_tpu.ops import attention as att
from deeplearning4j_tpu.ops import linear as ops
from deeplearning4j_tpu.ops import ring
from deeplearning4j_tpu.telemetry.trace import device_scope


@register_layer
@dataclass
class LayerNorm(Layer):
    """y = gamma * (x - mean) / sqrt(var + eps) + beta over the last axis."""

    eps: float = 1e-5

    sp_safe = True  # normalizes the feature axis only

    def output_type(self, input_type):
        return input_type

    def _nf(self, input_type):
        if isinstance(input_type, it.Recurrent):
            return input_type.size
        return input_type.arity()

    def init_params(self, rng, input_type):
        n = self._nf(input_type)
        return {
            "gamma": jnp.ones((n,), jnp.float32),
            "beta": jnp.zeros((n,), jnp.float32),
        }

    def regularizable(self, params):
        return {}

    def apply(self, params, x, *, state, train, rng, mask=None):
        mean = x.mean(axis=-1, keepdims=True)
        var = x.var(axis=-1, keepdims=True)
        y = (x - mean) / jnp.sqrt(var + self.eps)
        return y * params["gamma"] + params["beta"], state


@register_layer
@dataclass
class PositionEmbedding(Layer):
    """Adds position encodings to [b, t, f] activations.

    mode="learned": trainable [max_len, f] table (GPT-style).
    mode="sincos":  fixed sinusoidal encodings (Vaswani et al.), no params.
    Under sequence parallelism the time axis is sharded; the table is indexed
    with the global offset so every shard sees its true positions.
    """

    max_len: int = 512
    mode: str = "learned"  # learned | sincos

    sp_safe = True  # indexes the table at global offsets under seq sharding

    def output_type(self, input_type):
        return input_type

    def init_params(self, rng, input_type):
        if self.mode != "learned":
            return {}
        f = input_type.size
        w = init_mod.init(self.weight_init or "normal", rng,
                          (self.max_len, f), fan_in=f, fan_out=f)
        return {"pos": w * 0.02 if (self.weight_init or "normal") == "normal" else w}

    def regularizable(self, params):
        return {}

    def has_params(self):
        return self.mode == "learned"

    def _sincos(self, t, f, dtype):
        pos = jnp.arange(t, dtype=dtype)[:, None]
        i = jnp.arange(f // 2, dtype=dtype)[None, :]
        angle = pos / jnp.power(10000.0, 2 * i / f)
        emb = jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)
        if emb.shape[-1] < f:  # odd f
            emb = jnp.pad(emb, ((0, 0), (0, f - emb.shape[-1])))
        return emb

    def apply(self, params, x, *, state, train, rng, mask=None):
        b, t, f = x.shape
        axis = ring.active_sequence_axis()
        if axis is not None:
            off = jax.lax.axis_index(axis) * t
            t_global = t * jax.lax.axis_size(axis)
        else:
            off = 0
            t_global = t
        if self.mode == "learned":
            if t_global > self.max_len:
                # jnp.take under jit would silently clamp, duplicating the
                # last row's encoding for every position >= max_len; under
                # sequence parallelism the GLOBAL length (local t x shard
                # count, both static) is what must fit the table
                raise ValueError(
                    f"sequence length {t_global} exceeds PositionEmbedding "
                    f"max_len={self.max_len}")
            table = params["pos"]
            idx = off + jnp.arange(t)
            pe = jnp.take(table, idx, axis=0)
        else:
            if axis is not None and t_global > self.max_len:
                # the sincos table is generated max_len long under SP;
                # an out-of-range dynamic_slice would silently clamp
                raise ValueError(
                    f"sequence length {t_global} exceeds PositionEmbedding "
                    f"max_len={self.max_len} (sincos under seq sharding)")
            full = self._sincos(t if axis is None else self.max_len, f, x.dtype)
            pe = jax.lax.dynamic_slice_in_dim(full, off, t, axis=0) \
                if axis is not None else full[:t]
        return x + pe.astype(x.dtype)[None], state


@register_layer
@dataclass
class MultiHeadAttention(Layer):
    """Self-attention over [b, t, f]: fused qkv projection, SDPA (or ring
    attention under sequence parallelism), output projection.

    n_out defaults to n_in (residual-friendly). Key-padding `mask` [b, t]
    (1 = real token) masks keys; `causal` adds the autoregressive constraint.
    attention_impl: "auto" (sdpa, or ring when a sequence_parallel context is
    active), "blockwise" (O(t) memory flash recurrence on one chip).
    """

    n_heads: int = 8
    n_in: Optional[int] = None
    n_out: Optional[int] = None
    causal: bool = False
    attention_impl: str = "auto"
    block_size: int = 512
    attn_dropout: Optional[float] = None  # retain prob, DL4J convention

    sp_safe = True  # dispatches to ring attention under sequence_parallel

    def tensor_partition_specs(self, params, model_axis="model", model_size=1):
        """Megatron attention sharding: Wqkv column-parallel (heads split
        over the model axis when n_heads divides), Wo row-parallel so the
        per-shard head outputs reduce back with ONE psum (GSPMD inserts
        it). Requires head-aligned divisibility; otherwise replicate —
        always-correct fallback, same contract as the cuDNN helper
        fallthrough."""
        from jax.sharding import PartitionSpec as P

        specs = {k: P() for k in params}
        f = params["Wqkv"].shape[0]
        if (model_size > 1 and self.n_heads % model_size == 0
                and f % model_size == 0):
            specs["Wqkv"] = P(None, model_axis)
            specs["bqkv"] = P(model_axis)
            specs["Wo"] = P(model_axis, None)
            # bo replicated: it is added after the row-parallel reduce
        return specs

    def output_type(self, input_type):
        f = self.n_out or input_type.size
        return it.Recurrent(f, getattr(input_type, "timesteps", -1))

    def init_params(self, rng, input_type):
        f = self.n_in or input_type.size
        out = self.n_out or f
        if f % self.n_heads:
            raise ValueError(f"n_heads={self.n_heads} must divide d_model={f}")
        wi = self.weight_init or "xavier"
        r = jax.random.split(rng, 2)
        return {
            "Wqkv": init_mod.init(wi, r[0], (f, 3 * f), fan_in=f, fan_out=3 * f),
            "bqkv": jnp.zeros((3 * f,), jnp.float32),
            "Wo": init_mod.init(wi, r[1], (f, out), fan_in=f, fan_out=out),
            "bo": jnp.zeros((out,), jnp.float32),
        }

    def regularizable(self, params):
        return {k: v for k, v in params.items() if k.startswith("W")}

    def apply(self, params, x, *, state, train, rng, mask=None):
        b, t, f = x.shape
        h = self.n_heads
        d = f // h
        def heads(a):  # [b, t, f] -> [b, h, t, d]
            return a.reshape(b, t, h, d).transpose(0, 2, 1, 3)

        with device_scope("proj"):
            qkv = ops.bias_add(ops.dot(x, params["Wqkv"]), params["bqkv"])  # [b, t, 3f]
            q, k, v = (heads(a) for a in jnp.split(qkv, 3, axis=-1))
        with device_scope("attend"):
            o = att.attend(q, k, v, causal=self.causal, mask=mask,
                           impl=self.attention_impl, block_size=self.block_size)
        with device_scope("out"):
            o = o.transpose(0, 2, 1, 3).reshape(b, t, f)
            y = ops.bias_add(ops.dot(o, params["Wo"]), params["bo"])
            y = apply_dropout(y, self.attn_dropout if train else None, train, rng)
        # zero padded query positions like the RNN layers do
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, state


@register_layer
@dataclass
class TransformerBlock(Layer):
    """Pre-LN transformer block:
        x = x + MHA(LN(x));  x = x + W2·act(W1·LN(x)).
    One Layer so networks stay flat lists; params nest the sublayers'."""

    n_heads: int = 8
    n_in: Optional[int] = None
    ffn_mult: int = 4
    causal: bool = False
    attention_impl: str = "auto"
    eps: float = 1e-5

    sp_safe = True  # MHA rings, LN/FFN are per-timestep

    def tensor_partition_specs(self, params, model_axis="model", model_size=1):
        """Attention per MultiHeadAttention's rule; FFN Megatron-style:
        W1 column-parallel, W2 row-parallel (one psum at the block exit)."""
        from jax.sharding import PartitionSpec as P

        f = params["W1"].shape[0]
        hid = params["W1"].shape[1]
        specs = {
            "ln1": {k: P() for k in params["ln1"]},
            "attn": self._sub(f).tensor_partition_specs(
                params["attn"], model_axis, model_size),
            "ln2": {k: P() for k in params["ln2"]},
            "W1": P(), "b1": P(), "W2": P(), "b2": P(),
        }
        if model_size > 1 and hid % model_size == 0:
            specs["W1"] = P(None, model_axis)
            specs["b1"] = P(model_axis)
            specs["W2"] = P(model_axis, None)
        return specs

    def __post_init__(self):
        if self.activation is None:
            self.activation = "gelu"

    def output_type(self, input_type):
        return input_type

    def _sub(self, f):
        mha = MultiHeadAttention(n_heads=self.n_heads, n_in=f, causal=self.causal,
                                 attention_impl=self.attention_impl,
                                 weight_init=self.weight_init)
        return mha

    def init_params(self, rng, input_type):
        f = self.n_in or input_type.size
        hid = self.ffn_mult * f
        wi = self.weight_init or "xavier"
        r = jax.random.split(rng, 3)
        mha = self._sub(f)
        return {
            "ln1": {"gamma": jnp.ones((f,), jnp.float32),
                    "beta": jnp.zeros((f,), jnp.float32)},
            "attn": mha.init_params(r[0], input_type),
            "ln2": {"gamma": jnp.ones((f,), jnp.float32),
                    "beta": jnp.zeros((f,), jnp.float32)},
            "W1": init_mod.init(wi, r[1], (f, hid), fan_in=f, fan_out=hid),
            "b1": jnp.zeros((hid,), jnp.float32),
            "W2": init_mod.init(wi, r[2], (hid, f), fan_in=hid, fan_out=f),
            "b2": jnp.zeros((f,), jnp.float32),
        }

    def regularizable(self, params):
        out = {"W1": params["W1"], "W2": params["W2"]}
        out.update({"attn/" + k: v for k, v in params["attn"].items()
                    if k.startswith("W")})
        return out

    def _ln(self, p, x):
        mean = x.mean(axis=-1, keepdims=True)
        var = x.var(axis=-1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + self.eps) * p["gamma"] + p["beta"]

    def apply(self, params, x, *, state, train, rng, mask=None):
        f = x.shape[-1]
        mha = self._sub(f)
        with device_scope("norm"):
            xn = self._ln(params["ln1"], x)
        with device_scope(kind=type(mha).__name__):
            a, _ = mha.apply(params["attn"], xn, state={}, train=train, rng=rng,
                             mask=mask)
        x = x + a
        with device_scope("norm"):
            hminus = self._ln(params["ln2"], x)
        with device_scope("mlp"):
            hid = self.act_fn("gelu")(ops.bias_add(ops.dot(hminus, params["W1"]), params["b1"]))
            hid = apply_dropout(hid, self.dropout if train else None, train, rng)
            y = x + ops.bias_add(ops.dot(hid, params["W2"]), params["b2"])
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, state
