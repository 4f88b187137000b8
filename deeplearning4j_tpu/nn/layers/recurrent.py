"""Recurrent layers: LSTM, GravesLSTM (peepholes), GravesBidirectionalLSTM,
SimpleRnn, LastTimeStep wrapper.

Reference: nn/layers/recurrent/LSTMHelpers.java:785 (shared fwd/bwd math for
all 3 variants; per-timestep gemm hot loop :206-212), GravesLSTM.java,
GravesBidirectionalLSTM.java (fwd+bwd outputs are SUMMED, :224-225),
nn/conf/layers/{LSTM,GravesLSTM,GravesBidirectionalLSTM}.java.
cuDNN fused path: deeplearning4j-cuda CudnnLSTMHelper.java:612.

TPU-native formulation:
  * input projection for ALL timesteps hoisted into one [b*t, f]x[f, 4n]
    gemm (large MXU matmul), leaving only the [b, n]x[n, 4n] recurrent gemm
    inside `lax.scan` — the XLA analogue of cudnnRNNForwardTraining's fusion.
  * gate order (i, f, g, o): input, forget, cell-candidate, output — matches
    Keras HDF5 layout so model import is a direct slice-copy.
  * layout BTF [batch, time, features] (DL4J uses [b, f, t]).
  * masking: masked steps carry state through unchanged and output zeros.
  * stateful inference (rnnTimeStep, MultiLayerNetwork.java:2616) and tBPTT
    state carry (updateRnnStateWithTBPTTState :1474) via explicit
    init_carry/scan — the network threads carries functionally.

Cell math (peephole terms only for Graves variants):
    i = gate_act(x Wi + h Ri [+ pi*c_prev] + bi)
    f = gate_act(x Wf + h Rf [+ pf*c_prev] + bf)
    g = act(x Wg + h Rg + bg)
    c = f*c_prev + i*g
    o = gate_act(x Wo + h Ro [+ po*c] + bo)
    h = o * act(c)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn import activations as act_mod
from deeplearning4j_tpu.nn import initializers as init_mod
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.layers.base import (
    Layer,
    apply_dropout,
    column_parallel_specs,
    nested_layer,
    register_layer,
)
from deeplearning4j_tpu.ops import fused_lstm
from deeplearning4j_tpu.ops import linear as ops


class BaseRecurrent(Layer):
    """Adds the carry protocol used by tBPTT and rnnTimeStep."""

    # False for bidirectional layers: the backward scan needs the sequence
    # END, so chunked/streaming state carry is ill-defined (the reference
    # rejects rnnTimeStep/tBPTT for bidirectional layers)
    streamable = True

    n_out: int = 0

    def init_carry(self, batch: int):
        raise NotImplementedError

    def scan(self, params, x, carry, *, mask=None, train=False, rng=None):
        """x [b, t, f] -> (y [b, t, n], carry_out)."""
        raise NotImplementedError


def _lstm_scan(params, x, carry, gate_fn, act_fn, peephole: bool,
               mask=None, reverse: bool = False, prefix: str = ""):
    """Shared LSTM scan. params keys (optionally prefixed for bidirectional):
    W [f,4n], R [n,4n], b [4n], and pi/pf/po [n] if peephole."""
    W = params[prefix + "W"]
    R = params[prefix + "R"]
    b = params[prefix + "b"]
    # hoisted input projection: one big MXU gemm over all timesteps
    zx = ops.bias_add(ops.dot(x, W), b)  # [b, t, 4n]
    # carry dtype must match compute dtype (e.g. f64 gradient checks)
    carry = jax.tree_util.tree_map(lambda c: c.astype(zx.dtype), carry)
    # helper path (cuDNN-helper analogue, ConvolutionLayer.java:74-84
    # discovery pattern): the fused kernels know sigmoid/tanh cells only;
    # `fused_lstm` owns the rest of their admission
    if gate_fn is act_mod.get("sigmoid") and act_fn is act_mod.get("tanh"):
        peep = (tuple(params[prefix + k] for k in ("pi", "pf", "po"))
                if peephole else None)
        got = fused_lstm(zx, R, carry[0], carry[1], peep, mask, reverse)
        if got is not None:
            return got

    zx_t = jnp.swapaxes(zx, 0, 1)  # [t, b, 4n]
    if mask is not None:
        m_t = jnp.swapaxes(mask.astype(x.dtype), 0, 1)[..., None]  # [t, b, 1]
    else:
        m_t = None

    def cell(carry, inp):
        h_prev, c_prev = carry
        if m_t is None:
            z, = inp
            m = None
        else:
            z, m = inp
        z = z + ops.dot(h_prev, R)
        zi, zf, zg, zo = jnp.split(z, 4, axis=-1)
        if peephole:
            zi = zi + params[prefix + "pi"].astype(c_prev.dtype) * c_prev
            zf = zf + params[prefix + "pf"].astype(c_prev.dtype) * c_prev
        i = gate_fn(zi)
        f = gate_fn(zf)
        g = act_fn(zg)
        c = f * c_prev + i * g
        if peephole:
            zo = zo + params[prefix + "po"].astype(c.dtype) * c
        o = gate_fn(zo)
        h = o * act_fn(c)
        if m is not None:
            h = jnp.where(m > 0, h, 0.0)
            c = jnp.where(m > 0, c, c_prev)
            h_carry = jnp.where(m > 0, h, h_prev)
        else:
            h_carry = h
        return (h_carry, c), h

    xs = (zx_t,) if m_t is None else (zx_t, m_t)
    carry_out, ys = lax.scan(cell, carry, xs, reverse=reverse)
    return jnp.swapaxes(ys, 0, 1), carry_out  # [b, t, n]


def _lstm_partition_specs(params, model_axis, model_size, n_out,
                          prefixes=("",)):
    """Gate-block column split for LSTM params (the TP generalization of
    LSTMHelpers.java:206-212's per-timestep gemms): W [f,4n], R [n,4n] and
    b [4n] shard their gate axis over the model mesh axis, peepholes [n]
    follow. Gated on model_size | n_out so every per-gate [.., n] slice and
    peephole shards evenly; for power-of-two meshes that also keeps shard
    boundaries aligned with whole gate sub-blocks. Correctness never
    depends on the placement — GSPMD inserts the per-step collectives
    (the h-gather the hand-written TP recurrence would need) — the spec
    only decides what is sharded vs replicated."""
    from jax.sharding import PartitionSpec as P

    specs = {k: P() for k in params}
    if model_size > 1 and n_out % model_size == 0 and n_out >= 2 * model_size:
        for pre in prefixes:
            if pre + "W" in params:
                specs[pre + "W"] = P(None, model_axis)
            if pre + "R" in params:
                specs[pre + "R"] = P(None, model_axis)
            if pre + "b" in params:
                specs[pre + "b"] = P(model_axis)
            for pk in ("pi", "pf", "po"):
                if pre + pk in params:
                    specs[pre + pk] = P(model_axis)
    return specs


def _init_lstm_params(rng, n_in, n_out, weight_init, dist, forget_bias,
                      peephole: bool, prefix: str = ""):
    k_w, k_r, k_p = jax.random.split(rng, 3)
    wi = weight_init or "xavier"
    p = {
        prefix + "W": init_mod.init(wi, k_w, (n_in, 4 * n_out),
                                    fan_in=n_in, fan_out=4 * n_out, distribution=dist),
        prefix + "R": init_mod.init(wi, k_r, (n_out, 4 * n_out),
                                    fan_in=n_out, fan_out=4 * n_out, distribution=dist),
    }
    b = jnp.zeros((4 * n_out,), jnp.float32)
    # forget-gate bias init (DL4J forgetGateBiasInit, default 1.0)
    b = b.at[n_out : 2 * n_out].set(forget_bias)
    p[prefix + "b"] = b
    if peephole:
        p[prefix + "pi"] = jnp.zeros((n_out,), jnp.float32)
        p[prefix + "pf"] = jnp.zeros((n_out,), jnp.float32)
        p[prefix + "po"] = jnp.zeros((n_out,), jnp.float32)
    return p


@register_layer
@dataclass
class LSTM(BaseRecurrent):
    """No-peephole LSTM (nn/conf/layers/LSTM.java)."""

    n_in: Optional[int] = None
    n_out: int = 0
    gate_activation: str = "sigmoid"
    forget_gate_bias_init: float = 1.0

    _peephole = False

    def tensor_partition_specs(self, params, model_axis="model", model_size=1):
        return _lstm_partition_specs(params, model_axis, model_size,
                                     self.n_out)

    def output_type(self, input_type):
        t = input_type.timesteps if isinstance(input_type, it.Recurrent) else -1
        return it.Recurrent(self.n_out, t)

    def init_params(self, rng, input_type):
        n_in = self.n_in or input_type.size
        return _init_lstm_params(rng, n_in, self.n_out, self.weight_init,
                                 self.dist, self.forget_gate_bias_init,
                                 self._peephole)

    def regularizable(self, params):
        return {k: v for k, v in params.items() if k in ("W", "R")}

    def init_carry(self, batch):
        # distinct buffers — carries are donated into the tBPTT step, and
        # donating one buffer twice is an error
        return (jnp.zeros((batch, self.n_out), jnp.float32),
                jnp.zeros((batch, self.n_out), jnp.float32))

    def scan(self, params, x, carry, *, mask=None, train=False, rng=None):
        y, carry_out = _lstm_scan(
            params, x, carry,
            act_mod.get(self.gate_activation), self.act_fn("tanh"),
            self._peephole, mask=mask,
        )
        y = apply_dropout(y, self.dropout, train, rng)
        return y, carry_out

    def apply(self, params, x, *, state, train, rng, mask=None):
        y, _ = self.scan(params, x, self.init_carry(x.shape[0]),
                         mask=mask, train=train, rng=rng)
        return y, state


@register_layer
@dataclass
class GravesLSTM(LSTM):
    """Peephole LSTM (Graves 2013 formulation; nn/conf/layers/GravesLSTM.java)."""

    _peephole = True

    def regularizable(self, params):
        return {k: v for k, v in params.items() if k in ("W", "R")}


@register_layer
@dataclass
class GravesBidirectionalLSTM(BaseRecurrent):
    """Two independent peephole LSTMs run forward and backward over time;
    outputs are SUMMED (GravesBidirectionalLSTM.java:224-225), so nOut stays
    nOut (not 2x)."""

    streamable = False

    n_in: Optional[int] = None
    n_out: int = 0
    gate_activation: str = "sigmoid"
    forget_gate_bias_init: float = 1.0

    def tensor_partition_specs(self, params, model_axis="model", model_size=1):
        return _lstm_partition_specs(params, model_axis, model_size,
                                     self.n_out, prefixes=("f_", "b_"))

    def output_type(self, input_type):
        t = input_type.timesteps if isinstance(input_type, it.Recurrent) else -1
        return it.Recurrent(self.n_out, t)

    def init_params(self, rng, input_type):
        n_in = self.n_in or input_type.size
        k1, k2 = jax.random.split(rng)
        p = _init_lstm_params(k1, n_in, self.n_out, self.weight_init, self.dist,
                              self.forget_gate_bias_init, True, prefix="f_")
        p.update(_init_lstm_params(k2, n_in, self.n_out, self.weight_init,
                                   self.dist, self.forget_gate_bias_init, True,
                                   prefix="b_"))
        return p

    def regularizable(self, params):
        return {k: v for k, v in params.items() if k.endswith("W") or k.endswith("R")}

    def init_carry(self, batch):
        def z():
            return jnp.zeros((batch, self.n_out), jnp.float32)

        return ((z(), z()), (z(), z()))

    def scan(self, params, x, carry, *, mask=None, train=False, rng=None):
        gate = act_mod.get(self.gate_activation)
        act = self.act_fn("tanh")
        yf, cf = _lstm_scan(params, x, carry[0], gate, act, True,
                            mask=mask, prefix="f_")
        # The backward half is CHUNK-LOCAL under tBPTT: a reverse scan can
        # only start from the sequence (chunk) end, and the incoming carry
        # was produced at the START of the previous (earlier-in-time) chunk
        # — future context does not exist yet. So the reverse scan always
        # starts fresh; only the forward half carries across chunks.
        fresh = jax.tree_util.tree_map(jnp.zeros_like, carry[1])
        yb, cb = _lstm_scan(params, x, fresh, gate, act, True,
                            mask=mask, reverse=True, prefix="b_")
        y = apply_dropout(yf + yb, self.dropout, train, rng)
        return y, (cf, cb)

    def apply(self, params, x, *, state, train, rng, mask=None):
        y, _ = self.scan(params, x, self.init_carry(x.shape[0]),
                         mask=mask, train=train, rng=rng)
        return y, state


@register_layer
@dataclass
class SimpleRnn(BaseRecurrent):
    """Vanilla RNN: h_t = act(x W + h_{t-1} R + b). (Reference adds this in
    later versions; included for zoo/NLP breadth.)"""

    n_in: Optional[int] = None
    n_out: int = 0

    def tensor_partition_specs(self, params, model_axis="model", model_size=1):
        from jax.sharding import PartitionSpec as P

        specs = column_parallel_specs(params, model_axis, model_size)
        if len(specs.get("W", P())) > 0:  # W sharded -> R's output axis too
            specs["R"] = P(None, model_axis)
        return specs

    def output_type(self, input_type):
        t = input_type.timesteps if isinstance(input_type, it.Recurrent) else -1
        return it.Recurrent(self.n_out, t)

    def init_params(self, rng, input_type):
        n_in = self.n_in or input_type.size
        k_w, k_r = jax.random.split(rng)
        wi = self.weight_init or "xavier"
        return {
            "W": init_mod.init(wi, k_w, (n_in, self.n_out), distribution=self.dist),
            "R": init_mod.init(wi, k_r, (self.n_out, self.n_out), distribution=self.dist),
            "b": jnp.zeros((self.n_out,), jnp.float32),
        }

    def regularizable(self, params):
        return {k: v for k, v in params.items() if k in ("W", "R")}

    def init_carry(self, batch):
        return jnp.zeros((batch, self.n_out), jnp.float32)

    def scan(self, params, x, carry, *, mask=None, train=False, rng=None):
        act = self.act_fn("tanh")
        zx = ops.bias_add(ops.dot(x, params["W"]), params["b"])
        carry = carry.astype(zx.dtype)
        zx_t = jnp.swapaxes(zx, 0, 1)
        m_t = (jnp.swapaxes(mask.astype(x.dtype), 0, 1)[..., None]
               if mask is not None else None)

        def cell(h_prev, inp):
            if m_t is None:
                (z,) = inp
                m = None
            else:
                z, m = inp
            h = act(z + ops.dot(h_prev, params["R"]))
            if m is not None:
                h = jnp.where(m > 0, h, 0.0)
                h_carry = jnp.where(m > 0, h, h_prev)
            else:
                h_carry = h
            return h_carry, h

        xs = (zx_t,) if m_t is None else (zx_t, m_t)
        h_out, ys = lax.scan(cell, carry, xs)
        y = apply_dropout(jnp.swapaxes(ys, 0, 1), self.dropout, train, rng)
        return y, h_out

    def apply(self, params, x, *, state, train, rng, mask=None):
        y, _ = self.scan(params, x, self.init_carry(x.shape[0]),
                         mask=mask, train=train, rng=rng)
        return y, state


@register_layer
@dataclass
class LastTimeStep(Layer):
    """Wrapper: RNN [b,t,f] -> last (unmasked) step [b,f]
    (nn/conf/graph/rnn/LastTimeStepVertex.java as a layer)."""

    underlying: Optional[dict] = None  # serialized wrapped layer config

    def __post_init__(self):
        self._inner = nested_layer(self.underlying)

    def _wrapped(self):
        return self._inner

    def output_type(self, input_type):
        ot = self._inner.output_type(input_type) if self._inner else input_type
        return it.FeedForward(ot.size if isinstance(ot, it.Recurrent) else ot.arity())

    def init_params(self, rng, input_type):
        return self._inner.init_params(rng, input_type) if self._inner else {}

    def has_params(self):
        return self._inner.has_params() if self._inner else False

    def tensor_partition_specs(self, params, model_axis="model", model_size=1):
        if self._inner is not None:
            return self._inner.tensor_partition_specs(params, model_axis,
                                                      model_size)
        return super().tensor_partition_specs(params, model_axis, model_size)

    def propagate_mask(self, mask, input_type):
        return None

    def to_json(self):
        d = super().to_json()
        if self._inner is not None:
            d["underlying"] = self._inner.to_json()
        return d

    def apply(self, params, x, *, state, train, rng, mask=None):
        if self._inner is not None:
            x, state = self._inner.apply(params, x, state=state, train=train,
                                         rng=rng, mask=mask)
        if mask is not None:
            idx = jnp.clip(
                jnp.sum(mask.astype(jnp.int32), axis=1) - 1, 0, x.shape[1] - 1
            )
            y = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0, :]
        else:
            y = x[:, -1, :]
        return y, state
