"""Layer protocol: config + pure compute in one serializable object.

DL4J splits each layer into a declarative config (nn/conf/layers/*.java), a
param initializer (nn/params/*.java) and an imperative runtime
(nn/layers/**/*.java with hand-written activate()/backpropGradient()). In the
TPU-native design these collapse into ONE dataclass per layer:

    output_type(input)            InputType propagation  (conf side)
    init_params(rng, input)       param pytree           (ParamInitializer side)
    init_state(input)             mutable running state (BN stats); {} if none
    apply(params, x, ...)         pure forward; jax.grad supplies backprop

`apply` signature:
    apply(params, x, *, state, train, rng, mask) -> (y, new_state)
All layers must be jit-traceable: static python control flow only on config
fields, `lax` primitives for anything data-dependent.

Regularization contract (BaseLayer.calcL1/calcL2 in the reference): layers
expose `regularizable(params)` returning the sub-pytree subject to l1/l2
(weights but not biases, per DL4J defaults).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import activations as act_mod
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn import updaters as upd_mod
from deeplearning4j_tpu.util import jaxcompat

PyTree = Any

_LAYER_TYPES: Dict[str, type] = {}

#: the tag for a value the 'full' remat policy keeps: one that costs more to
#: compute again than to keep (the row groups' outputs, `hybrid.over_row_groups`;
#: the flash forward's output and logsumexp, in `ops/`; the q, k, v of
#: `hybrid.LatentAttention`). Defined where `ops/` can import it too.
REMAT_KEEP = jaxcompat.REMAT_KEEP


def register_layer(cls):
    """Class decorator: adds the layer to the serde registry."""
    _LAYER_TYPES[cls.__name__] = cls
    return cls


def layer_types() -> Dict[str, type]:
    return dict(_LAYER_TYPES)


@dataclass
class Layer:
    """Base layer config. Subclasses add fields; all fields must be
    JSON-serializable (or Schedule/Updater objects with to_json)."""

    # --- per-layer overrides (None = inherit from NeuralNetConfiguration) ---
    name: Optional[str] = None
    activation: Optional[str] = None
    weight_init: Optional[str] = None
    bias_init: Optional[float] = None
    l1: Optional[float] = None
    l2: Optional[float] = None
    l1_bias: Optional[float] = None
    l2_bias: Optional[float] = None
    updater: Optional[Any] = None          # Updater | str
    learning_rate: Optional[float] = None  # per-layer lr override
    dropout: Optional[Any] = None          # float retain-prob | IDropout obj
    weight_noise: Optional[Any] = None     # IWeightNoise (DropConnect etc.)
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: Optional[float] = None
    dist: Optional[dict] = None            # for weight_init == DISTRIBUTION
    constraints: Optional[list] = None
    #: activation-checkpoint policy for this layer's forward inside the
    #: train step: 'none' | 'dots_saveable' | 'full' | 'offload' (None =
    #: 'none'). Lowered to a jax.checkpoint policy by parallel/layout.py;
    #: a plain string so it serializes through to_json like every field.
    remat: Optional[str] = None

    # ---- shape/param/compute protocol ----
    def output_type(self, input_type: it.InputType) -> it.InputType:
        raise NotImplementedError

    def init_params(self, rng, input_type: it.InputType) -> PyTree:
        return {}

    def init_state(self, input_type: it.InputType) -> PyTree:
        return {}

    def apply(
        self,
        params: PyTree,
        x: jnp.ndarray,
        *,
        state: PyTree,
        train: bool,
        rng: Optional[jax.Array],
        mask: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, PyTree]:
        raise NotImplementedError

    def regularizable(self, params: PyTree) -> Dict[str, jnp.ndarray]:
        """Params subject to weight-decay (default: every key except biases)."""
        return {k: v for k, v in params.items() if not k.startswith("b")}

    def has_params(self) -> bool:
        return True

    def counter_summary(self, added: Dict[str, Any]) -> Tuple[str, Dict[str, Any]]:
        """For a layer that counts on the device (running totals in its
        state under ``counters``: telemetry/counters.py): the
        ``telemetry.fit_log()`` key a fit reports them under, and the entry
        made from what the fit ``added`` to each counter (numpy int64 or
        float64, at least 1-d). Default: the sums as they are."""
        return "counters", {k: v.tolist() if v.size > 1 else v.item()
                            for k, v in added.items()}

    # ---- parallelism protocol (net-new vs reference: SURVEY.md §2.4 —
    # the reference has data parallelism only, so these hooks have no
    # DL4J counterpart; they are what lets ParallelWrapper place ANY
    # config-DSL net on model/seq mesh axes, the any-model contract of
    # ParallelWrapper.java:59-73 generalized to tensor/sequence axes) ----

    #: True when the layer computes per-timestep (or is ring-aware), i.e.
    #: running it with the TIME axis sharded over a mesh 'seq' axis inside
    #: shard_map produces the same math as unsharded. Layers that reduce or
    #: scan over time (LSTM, pooling, 1d conv) must keep the default False
    #: so the sequence-parallel wrapper can refuse them loudly instead of
    #: silently computing chunk-local results.
    sp_safe = False

    def tensor_partition_specs(self, params: PyTree, model_axis: str = "model",
                               model_size: int = 1) -> PyTree:
        """PartitionSpec pytree (same structure as `params`) declaring how
        this layer's params shard over the tensor-parallel mesh axis.
        Default: replicate everything — always correct, never sharded.
        Layers with a known fan axis (Dense column-parallel,
        MultiHeadAttention head split + row-parallel output) override this;
        GSPMD inserts the activation collectives implied by the placement."""
        from jax.sharding import PartitionSpec as P

        return jax.tree_util.tree_map(lambda _: P(), params)

    def partition_specs(self, params: PyTree, axis_sizes, model_axis: str = "model") -> PyTree:
        """PartitionSpec pytree for this layer's params over a mesh whose axes
        have `axis_sizes` — what `parallel/mesh.py` places them by. Default:
        the tensor-parallel rule above on `model_axis`. A layer whose
        parameters live split over another axis says so here
        (`RoutedExperts`: its experts over its `exchange_axis`); a block hands
        the question down to the layers it wraps."""
        return self.tensor_partition_specs(params, model_axis, axis_sizes.get(model_axis, 1))

    # mask propagation: default passthrough (DL4J Layer.feedForwardMaskArray)
    def propagate_mask(
        self, mask: Optional[jnp.ndarray], input_type: it.InputType
    ) -> Optional[jnp.ndarray]:
        return mask

    # ---- config resolution helpers ----
    def act_fn(self, default: str = "identity") -> Callable:
        a = self.activation if self.activation is not None else default
        return act_mod.get(a)

    # ---- serde ----
    def to_json(self) -> dict:
        d = {"type": type(self).__name__}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if v is None:
                continue
            if isinstance(v, upd_mod.Updater):
                v = v.to_json()
            elif hasattr(v, "to_json") and not isinstance(v, (str, int, float)):
                v = v.to_json()
            d[f.name] = v
        return d

    @classmethod
    def from_json(cls, d: dict) -> "Layer":
        d = dict(d)
        t = d.pop("type")
        target = _LAYER_TYPES[t]
        if isinstance(d.get("updater"), dict):
            d["updater"] = upd_mod.from_json(d["updater"])
        if isinstance(d.get("dropout"), dict):
            from deeplearning4j_tpu.nn import dropout as drop_mod

            d["dropout"] = drop_mod.from_json(d["dropout"])
        if isinstance(d.get("weight_noise"), dict):
            from deeplearning4j_tpu.nn import weightnoise as wn_mod

            d["weight_noise"] = wn_mod.from_json(d["weight_noise"])
        field_names = {f.name for f in dataclasses.fields(target)}
        kwargs = {k: v for k, v in d.items() if k in field_names}
        obj = target(**kwargs)
        # tuple-ify list fields that started as tuples
        for f in dataclasses.fields(target):
            v = getattr(obj, f.name)
            if isinstance(v, list) and f.name in ("kernel_size", "stride", "padding", "dilation", "size", "pooling_dimensions"):
                setattr(obj, f.name, tuple(v))
        return obj


def nested_layer(value) -> Optional[Layer]:
    """A field that holds a LAYER (a wrapper's `underlying`, a block's `sub`),
    as a constructor is handed it: the Layer itself, or the dict its
    `to_json` wrote, which is how `Layer.from_json` passes it on (`to_json`
    writes any field that has a `to_json` through it). None stays None;
    anything else is refused."""
    if isinstance(value, dict):
        return Layer.from_json(value)
    if value is None or isinstance(value, Layer):
        return value
    raise TypeError(f"a Layer or its to_json dict, not {value!r}")


def column_parallel_specs(params: PyTree, model_axis: str,
                          model_size: int) -> PyTree:
    """Megatron column-parallel rule for W[..., n_out]/b[n_out] param dicts
    (Dense & friends): split the output-feature axis over the model axis
    when divisible and wide enough to be worth the collective; biases
    follow their weight. Everything else replicates."""
    from jax.sharding import PartitionSpec as P

    specs = {k: P() for k in params}
    w = params.get("W")
    if model_size > 1 and w is not None and jnp.ndim(w) >= 2:
        n_out = jnp.shape(w)[-1]
        if n_out % model_size == 0 and n_out >= 2 * model_size:
            specs["W"] = P(*([None] * (jnp.ndim(w) - 1)), model_axis)
            b = params.get("b")
            if b is not None and jnp.shape(b)[-1] == n_out:
                specs["b"] = P(model_axis)
    return specs


_ITERATION_TLS = __import__("threading").local()


class iteration_scope:
    """Makes the (traced) training-iteration scalar visible to layer-level
    transforms that take probability schedules — dropout p / weight-noise
    (IDropout.applyDropout(input, iteration, epoch) in the reference,
    nn/conf/dropout/Dropout.java:45-57). The train step wraps its loss/grad
    tracing in this scope; `apply` signatures stay clock-free. Thread-local:
    ParameterAveragingTrainingMaster worker threads trace their replicas'
    steps concurrently, and a shared global would leak one thread's tracer
    into another's program."""

    def __init__(self, iteration):
        self.iteration = iteration

    def __enter__(self):
        self._prev = getattr(_ITERATION_TLS, "value", None)
        _ITERATION_TLS.value = self.iteration
        return self

    def __exit__(self, *exc):
        _ITERATION_TLS.value = self._prev
        return False


def current_iteration():
    """The iteration scalar of the enclosing train-step trace, or None
    outside one (inference / gradient checks without a clock)."""
    return getattr(_ITERATION_TLS, "value", None)


def apply_dropout(x, dropout, train: bool, rng):
    """DL4J semantics: a float `dropout(p)` keeps activations with prob p and
    scales by 1/p (inverted dropout, nn/conf/dropout/Dropout.java); an
    IDropout object (AlphaDropout, GaussianDropout, GaussianNoise, ...)
    applies its own transform. Schedules on p/rate/stddev read the iteration
    from the enclosing `iteration_scope`."""
    if not train or dropout is None or rng is None:
        return x
    from deeplearning4j_tpu.nn import dropout as drop_mod

    obj = drop_mod.resolve(dropout)
    if obj is None:
        return x
    return obj.apply(x, rng, iteration=current_iteration())
