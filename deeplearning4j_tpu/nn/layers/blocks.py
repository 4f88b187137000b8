"""The residual blocks of a decoder: each WRAPS the layers it is given
(`base.nested_layer`: a Layer, or the dict its `to_json` wrote) behind RMS
pre-norms and residuals, and knows nothing of their arguments. Which mixer,
which feed-forward, built how, is the model's decision (`zoo/models.py`);
the mixers and the experts are `hybrid.py`'s and `ssm.py`'s.

  SubLayerBlock  y = x + sub(rms(x; w)), plain weight from one
  HybridBlock    h = x + mixer(rms(x)); y = h + moe(rms(h)), zero-centred
                 weights (the Qwen3-Next layer, ONE remat unit)

Each is one Layer, so that networks stay flat lists and `remat` wraps a
whole block; params nest the wrapped layers' trees, state and counters are
the wrapped layer's own (a `HybridBlock`'s: its `moe`'s). The wrapped
layer's device scope is its type's name (`dl4j.mamba2mixer`, ..); a dense
feed-forward (`GatedMLP`) is the block's `mlp`.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Union

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers import hybrid as hy
from deeplearning4j_tpu.nn.layers.base import Layer, nested_layer, register_layer
from deeplearning4j_tpu.telemetry.trace import device_scope

F32 = jnp.float32


def _wrapped(block: Layer, field: str) -> Layer:
    """The layer a block was given, refused if it is none."""
    layer = nested_layer(getattr(block, field))
    if layer is None:
        raise TypeError(f"{type(block).__name__}.{field}: a Layer (or its to_json dict), not None")
    return layer


def _handed_down(block: Layer, layer: Layer) -> Layer:
    """`layer` as it draws its parameters: with what a layer inherits and
    the block was given (`weight_init`), where it has none of its own."""
    if block.weight_init is None or layer.weight_init is not None:
        return layer
    return dataclasses.replace(layer, weight_init=block.weight_init)


@register_layer
@dataclass
class SubLayerBlock(Layer):
    """y = x + sub(rms(x; w)) around ANY layer `sub` that keeps its input's
    type (required: the default is there because the fields before it have
    one); `eps` is the pre-norm's. Params `norm`, `sub`."""

    sub: Optional[Union[Layer, dict]] = None
    eps: float = 1e-5

    def __post_init__(self):
        self.sub = _wrapped(self, "sub")

    def output_type(self, input_type):
        return input_type

    def init_params(self, rng, input_type):
        return {"norm": {"w": jnp.ones((input_type.size,), F32)},
                "sub": _handed_down(self, self.sub).init_params(rng, input_type)}

    def init_state(self, input_type):
        return self.sub.init_state(input_type)

    def counter_summary(self, added):
        return self.sub.counter_summary(added)

    def regularizable(self, params):
        return {"sub/" + k: v for k, v in self.sub.regularizable(params["sub"]).items()}

    def apply(self, params, x, *, state, train, rng, mask=None):
        with device_scope("norm"):
            xn = hy.rms_norm(x, params["norm"]["w"], self.eps, zero_centered=False)
        # a dense feed-forward is the block's `mlp`; a mixer or the experts
        # open parts of their own under their kind
        with (device_scope("mlp") if isinstance(self.sub, hy.GatedMLP)
              else device_scope(kind=type(self.sub).__name__)):
            a, state = self.sub.apply(params["sub"], xn, state=state, train=train, rng=rng,
                                      mask=mask)
        return x + a, state


@register_layer
@dataclass
class HybridBlock(Layer):
    """h = x + mixer(rms(x)); y = h + moe(rms(h)); `eps` is both
    pre-norms'. Params `norm1`, `mixer`, `norm2`, `moe`; the state is
    `moe`'s, the mixer runs without one."""

    mixer: Optional[Union[Layer, dict]] = None
    moe: Optional[Union[Layer, dict]] = None
    eps: float = 1e-6

    def __post_init__(self):
        self.mixer, self.moe = _wrapped(self, "mixer"), _wrapped(self, "moe")

    def output_type(self, input_type):
        return input_type

    def init_params(self, rng, input_type):
        f = input_type.size
        r = jax.random.split(rng, 2)
        return {"norm1": {"w": jnp.zeros((f,), F32)},
                "mixer": _handed_down(self, self.mixer).init_params(r[0], input_type),
                "norm2": {"w": jnp.zeros((f,), F32)},
                "moe": _handed_down(self, self.moe).init_params(r[1], input_type)}

    def init_state(self, input_type):
        return self.moe.init_state(input_type)

    def counter_summary(self, added):
        return self.moe.counter_summary(added)

    def regularizable(self, params):
        out = {"mixer/" + k: v for k, v in self.mixer.regularizable(params["mixer"]).items()}
        out.update({"moe/" + k: v for k, v in self.moe.regularizable(params["moe"]).items()})
        return out

    def apply(self, params, x, *, state, train, rng, mask=None):
        with device_scope("norm"):
            xn = hy.rms_norm(x, params["norm1"]["w"], self.eps)
        with device_scope(kind=type(self.mixer).__name__):
            a, _ = self.mixer.apply(params["mixer"], xn, state={}, train=train, rng=rng,
                                    mask=mask)
        h = x + a
        with device_scope("norm"):
            hn = hy.rms_norm(h, params["norm2"]["w"], self.eps)
        with device_scope(kind=type(self.moe).__name__):
            m, state = self.moe.apply(params["moe"], hn, state=state, train=train, rng=rng,
                                      mask=mask)
        return h + m, state
