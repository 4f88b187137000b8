"""Per-tensor parameter layouts over the fsdp x model mesh axes + the
selectable activation-checkpoint (remat) policy registry.

This module is the ONE place (with parallel/mesh.py) that constructs
PartitionSpec/NamedSharding objects for the runtime packages — jaxlint
JX018 enforces that every other models/parallel/training/distributed
site routes through here, so the fsdp axis can never be silently
bypassed by a hand-rolled spec.

Layout rules (SpecLayout): every parameter class maps to a spec over
`fsdp` x `model`:

    embedding tables    [vocab, d]        -> P('fsdp', None)   (vocab split)
    dense kernels       [n_in, n_out]     -> P('fsdp', 'model') when the
                        layer declares column-parallel tp, else P('fsdp', None)
    conv kernels        [kh, kw, cin, cout] -> fsdp on the largest free
                        divisible axis (cin, typically), tp on cout
    attention proj      Wqkv [d, 3d] / Wo [d, d] -> fsdp on the axis the
                        layer-declared tp spec left free
    norms / biases      1-D vectors       -> P() replicated (the all-gather
                        for a vector costs more than the bytes it frees;
                        same policy as mesh.param_partition_spec)

The tp placement itself stays LAYER-DECLARED (Layer.tensor_partition_specs
via mesh.model_param_shardings); SpecLayout composes the fsdp axis onto
whatever the layer declared, so dp/tp configs are unchanged when fsdp=1.

Gather-on-use (ZeRO-3 dataflow): parameters LIVE sharded over fsdp in HBM;
inside the jitted train step each layer's subtree is constrained back to
its fsdp-free spec right before use (`FsdpArrangement.gather`), so XLA
places one per-layer all-gather next to that layer's compute and overlaps
the two; the constraint runs INSIDE the layer's remat scope, so the
backward pass RE-gathers instead of stashing full-width weights as
residuals. Gradients are constrained back to the sharded spec before the
updater (`shard_tree`), which XLA fuses with the data-axis psum into a
reduce-scatter; optimizer moments mirror the param shardings
(mesh.mirror_opt_shardings), so the whole (params, grads, opt) triple
stays 1/fsdp-sized at rest.

Remat policies (docs/PERFORMANCE.md policy table): layer configs select a
policy BY NAME — names lower to jax.checkpoint policies here:

    'none'            no checkpointing: full activation stash
    'dots_saveable'   save matmul outputs, recompute elementwise
    'full'            recompute the block; save nothing but what is tagged REMAT_KEEP
                      (a value that costs more to compute again than to keep: the
                      row groups' outputs, the flash forward's output and
                      logsumexp, a latent-attention layer's q, k, v, a
                      routed-expert layer's first product within a byte bound,
                      its sort, its router's logits)
    'offload'         save dot outputs to host memory (pinned_host)

Booleans stay accepted where the old single `remat: bool` flag lived
(parallel/transformer.py): True == 'full', False == 'none'.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel import mesh as mesh_mod
from deeplearning4j_tpu.util.jaxcompat import (  # noqa: F401 — named from here by the runtime packages
    REMAT_POLICY_NAMES,
    canonical_policy,
    maybe_remat,
    remat_policy,
)

# The remat policy registry (REMAT_POLICY_NAMES, canonical_policy, remat_policy,
# maybe_remat) stands in util/jaxcompat.py beside `REMAT_KEEP`, where `nn/` can
# reach it too; the runtime packages name it from here.

#: modeled fraction of the full activation stash each policy keeps —
#: nn/memory.py and the analyzer read this so static estimates and the
#: runtime watermark speak the same language. 'full' uses the
#: sqrt-schedule 2*sqrt(n)/n at n layers (see memory.remat_activation_factor),
#: so its entry here is the n-independent floor.
REMAT_ACT_FRACTION = {
    "none": 1.0,
    "dots_saveable": 2.0 / 3.0,
    "full": None,   # depth-dependent: min(1, 2*sqrt(n)/n)
    "offload": 0.1,  # only the live block's working set stays in HBM
}


# ---------------------------------------------------------------------------
# fsdp spec layout
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SpecLayout:
    """Per-tensor layout rules over the fsdp/model axes. `extend` takes a
    LAYER-DECLARED tensor-parallel spec and adds the fsdp axis on the
    largest free, divisible dimension — embedding tables split their
    vocab axis, dense/attention kernels their input axis, conv kernels
    their channel axis; vectors (norm scales, biases) replicate."""

    fsdp_axis: str = "fsdp"
    model_axis: str = "model"

    def extend(self, spec: P, shape: Tuple[int, ...], fsdp_size: int) -> P:
        if fsdp_size <= 1 or len(shape) < 2:
            return spec
        entries = list(spec) + [None] * (len(shape) - len(spec))
        best = None  # (size, dim)
        for dim, size in enumerate(shape):
            if entries[dim] is not None:
                continue  # dim already carries a mesh axis (tp)
            if size % fsdp_size or size < 2 * fsdp_size:
                continue
            if best is None or size > best[0]:
                best = (size, dim)
        if best is None:
            return spec
        entries[best[1]] = self.fsdp_axis
        return P(*entries)

    def drop_fsdp(self, spec: P) -> P:
        """The gather-on-use target: the same spec with the fsdp axis
        removed (tp placement intact)."""
        def strip(e):
            if e == self.fsdp_axis:
                return None
            if isinstance(e, (tuple, list)):
                kept = tuple(a for a in e if a != self.fsdp_axis)
                return kept if kept else None
            return e

        return P(*[strip(e) for e in spec])


DEFAULT_LAYOUT = SpecLayout()


def fsdp_param_specs(mesh: Mesh, model,
                     layout: SpecLayout = DEFAULT_LAYOUT):
    """Per-key PartitionSpec trees for a MultiLayerNetwork/ComputationGraph:
    the layer-declared tensor-parallel specs (mesh.model_param_shardings)
    with the fsdp axis composed on by `layout.extend`. Returns
    {key: P-tree} matching model.params' top-level keys."""
    fsdp_size = mesh.shape.get(layout.fsdp_axis, 1)
    base = mesh_mod.model_param_shardings(mesh, model)

    def one(sharding_tree, param_tree):
        return jax.tree_util.tree_map(
            lambda sh, p: layout.extend(sh.spec, np.shape(p), fsdp_size),
            sharding_tree, param_tree)

    return {k: one(base[k], model.params[k]) for k in base}


def fsdp_param_shardings(mesh: Mesh, specs):
    """NamedSharding trees from `fsdp_param_specs` output (for device_put /
    mirror_opt_shardings)."""
    return {
        k: jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), tree,
            is_leaf=lambda n: isinstance(n, P))
        for k, tree in specs.items()
    }


def specs_beyond(shardings, model_axis: str = "model"):
    """The PartitionSpec trees of `shardings` ({key: NamedSharding tree}) when
    some leaf is split over another axis than `model_axis` — a layer's own
    declaration (`Layer.partition_specs`) that the step has to pin as it pins
    the fsdp axis —, else None."""
    def names(spec):
        return {a for e in spec if e is not None
                for a in (e if isinstance(e, (tuple, list)) else (e,))}

    leaves = jax.tree_util.tree_leaves(shardings)
    if not any(names(sh.spec) - {model_axis} for sh in leaves):
        return None
    return {k: jax.tree_util.tree_map(lambda sh: sh.spec, tree)
            for k, tree in shardings.items()}


class FsdpArrangement:
    """Attached to a model (as `model._fsdp_layout`) by ParallelWrapper
    when the mesh's fsdp axis is >1, or when a layer declared leaves split
    over another axis than the tensor-parallel one (`specs_beyond`: expert
    matrices over their exchange axis; `gather` then changes nothing and
    `shard_tree` keeps gradients, parameters and moments split as they
    rest). The model's functional core consults
    it at trace time: `gather` constrains one layer/vertex subtree to its
    fsdp-free spec right before use (the per-layer all-gather XLA overlaps
    with that layer's compute), `shard_tree` constrains a params/grads
    tree back to the sharded-at-rest specs (the reduce-scatter seam)."""

    def __init__(self, mesh: Mesh, specs,
                 layout: SpecLayout = DEFAULT_LAYOUT):
        self.mesh = mesh
        self.layout = layout
        self.specs = specs          # {key: P-tree}, sharded-at-rest
        self.gathered = {k: jax.tree_util.tree_map(
            layout.drop_fsdp, tree, is_leaf=lambda n: isinstance(n, P))
            for k, tree in specs.items()}

    def _constrain(self, subtree, spec_tree):
        mesh = self.mesh
        return jax.tree_util.tree_map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, s)),
            subtree, spec_tree)

    def gather(self, key: str, subtree):
        """Gather-on-use: constrain one top-level param subtree to its
        fsdp-free (tp-only) spec. No-op for keys the layout never saw."""
        spec = self.gathered.get(key)
        if spec is None:
            return subtree
        return self._constrain(subtree, spec)

    def scatter(self, key: str, subtree):
        spec = self.specs.get(key)
        if spec is None:
            return subtree
        return self._constrain(subtree, spec)

    def shard_tree(self, tree):
        """Constrain a whole params/grads tree (dict keyed like
        model.params) to the sharded-at-rest specs: on gradients this is
        the reduce-scatter seam; on updated params it pins the scan-carry
        sharding so the K-window program's carry stays fsdp-sharded."""
        return {k: self.scatter(k, v) for k, v in tree.items()}
