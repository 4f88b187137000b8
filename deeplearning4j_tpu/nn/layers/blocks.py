"""The residual blocks of a decoder: each WRAPS the layers it is given
(`base.nested_layer`: a Layer, or the dict its `to_json` wrote) behind RMS
pre-norms and residuals, and knows nothing of their arguments. Which mixer,
which feed-forward, built how, is the model's decision (`zoo/models.py`);
the mixers and the experts are `hybrid.py`'s and `ssm.py`'s.

  SubLayerBlock  y = x + sub(rms(x; w)), plain weight from one; with
                 `post_norm` the sandwich y = x + rms(sub(rms(x; w)); w_out)
  HybridBlock    h = x + mixer(rms(x)); y = h + moe(rms(h)), zero-centred
                 weights (the Qwen3-Next layer, ONE remat unit)
  LoopedStack    a list of such blocks applied `steps` times over ONE set of
                 parameters; the passes' outputs stacked [b, steps, t, f]

Each is one Layer, so that networks stay flat lists and `remat` wraps a
whole block; params nest the wrapped layers' trees, state and counters are
the wrapped layer's own (a `HybridBlock`'s: its `moe`'s; a `LoopedStack`
refuses a layer that keeps any). The wrapped layer's device scope is its
type's name (`dl4j.mamba2mixer`, ..); a dense feed-forward (`GatedMLP`) is
the block's `mlp`.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import List, Optional, Union

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.layers import hybrid as hy
from deeplearning4j_tpu.nn.layers.base import Layer, nested_layer, register_layer
from deeplearning4j_tpu.telemetry.trace import device_scope
from deeplearning4j_tpu.util import jaxcompat

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


def _specs_of(block: Layer, params, axis_sizes, model_axis, **wrapped):
    """A block's `partition_specs`: each wrapped layer's own for its subtree
    (an expert layer's matrices over its exchange axis), the block's norms
    whole on every device."""
    specs = Layer.partition_specs(block, params, axis_sizes, model_axis)
    for name, layer in wrapped.items():
        specs[name] = layer.partition_specs(params[name], axis_sizes, model_axis)
    return specs


@register_layer
@dataclass
class SubLayerBlock(Layer):
    """y = x + sub(rms(x; w)) around ANY layer `sub` that keeps its input's
    type (required: the default is there because the fields before it have
    one); `eps` is the pre-norm's. With `post_norm` the sub-layer's output is
    normed too before it is added (the sandwich norm): y = x +
    rms(sub(rms(x; w)); w_out), same `eps`. Params `norm`, `sub` and, only
    with `post_norm`, `norm_out`."""

    sub: Optional[Union[Layer, dict]] = None
    eps: float = 1e-5
    post_norm: bool = False

    def __post_init__(self):
        self.sub = _wrapped(self, "sub")

    def output_type(self, input_type):
        return input_type

    def init_params(self, rng, input_type):
        p = {"norm": {"w": jnp.ones((input_type.size,), F32)},
             "sub": _handed_down(self, self.sub).init_params(rng, input_type)}
        if self.post_norm:
            p["norm_out"] = {"w": jnp.ones((input_type.size,), F32)}
        return p

    def init_state(self, input_type):
        return self.sub.init_state(input_type)

    def counter_summary(self, added):
        return self.sub.counter_summary(added)

    def regularizable(self, params):
        return {"sub/" + k: v for k, v in self.sub.regularizable(params["sub"]).items()}

    def partition_specs(self, params, axis_sizes, model_axis="model"):
        return _specs_of(self, params, axis_sizes, model_axis, sub=self.sub)

    def apply(self, params, x, *, state, train, rng, mask=None):
        with device_scope("norm"):
            xn = hy.rms_norm(x, params["norm"]["w"], self.eps, zero_centered=False)
        # a dense feed-forward is the block's `mlp`; a mixer or the experts
        # open parts of their own under their kind
        with (device_scope("mlp") if isinstance(self.sub, hy.GatedMLP)
              else device_scope(kind=type(self.sub).__name__)):
            a, state = self.sub.apply(params["sub"], xn, state=state, train=train, rng=rng,
                                      mask=mask)
        if self.post_norm:
            with device_scope("norm"):
                a = hy.rms_norm(a, params["norm_out"]["w"], self.eps, zero_centered=False)
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

    def partition_specs(self, params, axis_sizes, model_axis="model"):
        return _specs_of(self, params, axis_sizes, model_axis, mixer=self.mixer, moe=self.moe)

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


@register_layer
@dataclass
class LoopedStack(Layer):
    """`layers` (Layers, or the dicts their `to_json` wrote) applied in order
    `steps` times over ONE set of parameters: pass s + 1 reads what pass s
    wrote, and every leaf's gradient is the sum of its `steps` uses. x
    [b, t, f] -> the passes' outputs stacked [b, steps, t, f]
    (`inputs.RecurrentPasses`: the batch axis stays leading). Params
    {"0": .., "1": ..}: ONE pass's. Each nested layer keeps its input's type,
    is run under its own device scope with `layer` its index in the list,
    gets its own `remat` around each of its applications and a fresh rng a
    pass; the mask is handed to every one unchanged. A nested layer that
    keeps state (running statistics, counters) is refused by name: what a
    second pass should do with the first's is not decided here.

    The passes are unrolled: as one `lax.scan` (the weights closed over, the
    state the carry) XLA holds the stacked residuals twice — the forward
    loop's outputs and the backward loop's carry — and the step that fits a
    chip unrolled does not fit it scanned (PERF.md section 6, PR 44)."""

    layers: Optional[List[Union[Layer, dict]]] = None
    steps: int = 1

    def __post_init__(self):
        if not self.layers or any(l is None for l in self.layers):
            raise TypeError(f"LoopedStack.layers: a list of Layers (or their to_json dicts), "
                            f"not {self.layers!r}")
        if self.steps < 1:
            raise ValueError(f"LoopedStack.steps: at least one pass, not {self.steps}")
        self.layers = [nested_layer(l) for l in self.layers]

    def to_json(self):
        d = super().to_json()
        d["layers"] = [l.to_json() for l in self.layers]
        return d

    def _checked(self, input_type):
        for j, layer in enumerate(self.layers):
            what = f"LoopedStack.layers[{j}] ({type(layer).__name__})"
            if layer.output_type(input_type) != input_type:
                raise ValueError(f"{what} does not keep its input's type {input_type}: "
                                 f"its output could not feed the next pass")
            if jax.tree_util.tree_leaves(layer.init_state(input_type)):
                raise ValueError(f"{what} keeps state, which a looped stack does not "
                                 f"thread from pass to pass")

    def output_type(self, input_type):
        if not isinstance(input_type, it.Recurrent) or isinstance(input_type, it.RecurrentPasses):
            raise ValueError(f"LoopedStack runs over [b, t, f], not {input_type}")
        self._checked(input_type)
        return it.RecurrentPasses(input_type.size, input_type.timesteps, passes=self.steps)

    def init_params(self, rng, input_type):
        keys = jax.random.split(rng, len(self.layers))
        return {str(j): (_handed_down(self, layer).init_params(keys[j], input_type)
                         if layer.has_params() else {})
                for j, layer in enumerate(self.layers)}

    def regularizable(self, params):
        return {f"{j}/{k}": v for j, layer in enumerate(self.layers)
                for k, v in layer.regularizable(params[str(j)]).items()}

    def apply(self, params, x, *, state, train, rng, mask=None):
        def run(j, layer):
            def one(p, xx, r):
                with device_scope(kind=type(layer).__name__, layer=j):
                    return layer.apply(p, xx, state={}, train=train, rng=r, mask=mask)[0]
            return jaxcompat.maybe_remat(one, layer.remat) if train and layer.remat else one

        runs = [run(j, layer) for j, layer in enumerate(self.layers)]

        def one_pass(h, r):
            rs = [None] * len(runs) if r is None else jax.random.split(r, len(runs))
            for j, f in enumerate(runs):
                h = f(params[str(j)], h, rs[j])
            return h

        rngs = None if rng is None else jax.random.split(rng, self.steps)
        passes = []
        for s in range(self.steps):
            x = one_pass(x, None if rngs is None else rngs[s])
            passes.append(x)
        return jnp.stack(passes, axis=1), state
