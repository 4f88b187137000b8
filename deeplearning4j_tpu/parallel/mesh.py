"""Device mesh construction + sharding rules.

The reference scales with ParallelWrapper threads pinned to GPUs
(deeplearning4j-scaleout-parallelwrapper ParallelWrapper.java:59-73) and an
Aeron parameter server across hosts (SharedTrainingMaster.java:451-469). The
TPU-native replacement (SURVEY.md §5 'Distributed communication backend') is a
`jax.sharding.Mesh` over ICI/DCN with XLA-inserted collectives: data-parallel
gradients ride a psum instead of the EncodedGradientsAccumulator fan-out, and
tensor-parallel layer shards replace nothing in the reference (net-new
capability, Megatron-style column split on the last weight axis).

Axes (any may be 1): dcn / data / fsdp / model / pipe / seq / expert. The
'dcn' axis is OUTERMOST (slowest-varying): in a multi-host job jax.devices()
orders same-process devices contiguously, so reshaping hosts-first puts
cross-host (DCN) traffic on the leading axis and keeps every inner axis on
ICI — the large-scale-TF placement (PAPERS.md 1603.04467) where only the
data/replica dimension crosses the slow network. The 'fsdp' axis sits
between 'data' and 'model': parameter/optimizer shards (ZeRO-3 style
gather-on-use, parallel/layout.py) ride ICI next to the tensor axis, while
the batch hierarchy (dcn·data) stays outermost.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("dcn", "data", "fsdp", "model", "pipe", "seq", "expert")


@dataclass
class MeshSpec:
    # declared in keyword order that predates the dcn/fsdp axes; every call
    # site constructs MeshSpec by keyword, and AXES (not field order) fixes
    # the mesh layout, so appending keeps old specs byte-compatible
    data: int = 1
    model: int = 1
    pipe: int = 1
    seq: int = 1
    expert: int = 1
    dcn: int = 1
    fsdp: int = 1

    def total(self) -> int:
        return (self.dcn * self.data * self.fsdp * self.model * self.pipe
                * self.seq * self.expert)

    def axis_sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in AXES}

    @staticmethod
    def data_parallel(n: Optional[int] = None) -> "MeshSpec":
        return MeshSpec(data=n or len(jax.devices()))


def build_mesh(spec: Optional[MeshSpec] = None,
               devices: Optional[Sequence] = None) -> Mesh:
    """Build a Mesh over `devices` (default: all local). Axes of size 1 are
    kept in the mesh so PartitionSpecs stay stable across topologies."""
    devices = list(devices if devices is not None else jax.devices())
    spec = spec or MeshSpec.data_parallel(len(devices))
    if spec.total() != len(devices):
        raise ValueError(
            f"mesh spec {spec.axis_sizes()} needs {spec.total()} devices, "
            f"have {len(devices)}"
        )
    arr = np.array(devices).reshape(
        spec.dcn, spec.data, spec.fsdp, spec.model, spec.pipe, spec.seq,
        spec.expert
    )
    return Mesh(arr, AXES)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """Shard axis 0 over 'data' (and leave the rest replicated)."""
    return NamedSharding(mesh, P("data", *([None] * (ndim - 1))))


def shard_batch_tree(mesh: Mesh, tree):
    """device_put a pytree of host arrays with axis-0 'data' sharding."""
    def put(x):
        if x is None:
            return None
        sh = NamedSharding(mesh, P("data", *([None] * (x.ndim - 1))))
        return jax.device_put(x, sh)

    return jax.tree_util.tree_map(put, tree)


def param_partition_spec(path: str, shape: Tuple[int, ...],
                         model_size: int) -> P:
    """Tensor-parallel rule: split the last (output/feature) axis over 'model'
    when divisible and large enough to be worth the collective — the
    column-parallel scheme; everything else replicates.

    Biases and small vectors stay replicated (an all-gather would cost more
    than the memory saved)."""
    if model_size <= 1 or not shape:
        return P()
    last = shape[-1]
    if len(shape) >= 2 and last % model_size == 0 and last >= 2 * model_size:
        return P(*([None] * (len(shape) - 1)), "model")
    return P()


def model_param_shardings(mesh: Mesh, model, model_axis: str = "model"):
    """NamedSharding tree for a MultiLayerNetwork / ComputationGraph's
    params built from LAYER-DECLARED rules (Layer.partition_specs: the
    tensor-parallel Layer.tensor_partition_specs, and what a layer keeps
    split over another axis — an expert layer's matrices over its exchange
    axis) — the any-model contract of
    ParallelWrapper.java:59-73 extended to the model axis: Dense layers
    column-split, MultiHeadAttention head-splits + row-parallel output,
    TransformerBlock FFN Megatron-splits, everything else replicates.
    Models without a layer structure fall back to the generic last-axis
    rule (shard_params_tree)."""
    def spec_to_sharding(tree):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), tree,
            is_leaf=lambda n: isinstance(n, P))

    if hasattr(model, "layers") and isinstance(getattr(model, "params"), dict):
        out = {}
        for i, layer in enumerate(model.layers):
            k = f"layer_{i}"
            out[k] = spec_to_sharding(layer.partition_specs(
                model.params[k], dict(mesh.shape), model_axis))
        return out
    if hasattr(model, "topo") and hasattr(model.conf, "vertices"):
        from deeplearning4j_tpu.nn.graph_vertices import LayerVertex

        out = {}
        for name in model.topo:
            v = model.conf.vertices[name]
            if isinstance(v, LayerVertex):
                out[name] = spec_to_sharding(v.layer.partition_specs(
                    model.params[name], dict(mesh.shape), model_axis))
            else:
                out[name] = jax.tree_util.tree_map(
                    lambda _: NamedSharding(mesh, P()), model.params[name])
        return out
    return shard_params_tree(mesh, model.params, model_axis)


def mirror_opt_shardings(mesh: Mesh, opt_entry, param_shardings):
    """Sharding tree for ONE updater-state entry: moment subtrees that
    structurally mirror the params (Adam m/v, momentum v, ...) inherit the
    param shardings; scalars and anything else replicate."""
    repl = NamedSharding(mesh, P())

    def mirrors(tree) -> bool:
        # exact structure equality — a prefix match would wrongly treat a
        # scalar slot (Adam's t) as mirroring the whole param tree
        return (jax.tree_util.tree_structure(tree)
                == jax.tree_util.tree_structure(param_shardings))

    if isinstance(opt_entry, dict):
        return {k: (param_shardings if mirrors(v)
                    else jax.tree_util.tree_map(lambda _: repl, v))
                for k, v in opt_entry.items()}
    return jax.tree_util.tree_map(lambda _: repl, opt_entry)


def shard_params_tree(mesh: Mesh, params, model_axis: str = "model"):
    """Apply param_partition_spec across a param pytree; returns the matching
    NamedSharding tree (for in_shardings / device_put)."""
    model_size = mesh.shape[model_axis]

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    shardings = []
    for path, leaf in flat:
        pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        spec = param_partition_spec(pstr, np.shape(leaf), model_size)
        shardings.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, shardings)
