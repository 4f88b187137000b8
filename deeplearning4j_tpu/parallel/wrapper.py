"""ParallelWrapper — multi-device training orchestrator.

Reference: deeplearning4j-scaleout-parallelwrapper ParallelWrapper.java:59-73
(TrainingMode AVERAGING / SHARED_GRADIENTS; fit loop :185-264 round-robins
batches to per-device replica threads, averaging params every
`averaging_frequency` iterations) and the SHARED_GRADIENTS path through
EncodedGradientsAccumulator (SURVEY.md §3.3). The reference contract is
any-model: the wrapper takes any Model (`ParallelWrapper.java:59-73`), and
this wrapper keeps that contract for the net-new axes too.

TPU-native redesign: one process, one jitted SPMD program over a Mesh.
  * data axis — global batch sharded over 'data'; XLA inserts the gradient
    all-reduce (psum over ICI) where the reference broadcast encoded
    gradients through queues. Mathematically = SHARED_GRADIENTS with
    threshold 0 and = AVERAGING with frequency 1, minus the staleness.
  * model axis (net-new) — tensor parallelism from LAYER-DECLARED rules
    (Layer.tensor_partition_specs): Dense column-splits, MultiHeadAttention
    head-splits with a row-parallel output projection, TransformerBlock
    Megatron-splits its FFN. Params and mirrored updater moments are
    placed with those NamedShardings; GSPMD propagates and inserts the
    activation collectives. Works for MultiLayerNetwork, ComputationGraph
    and every zoo/imported net — no bespoke model class required.
  * seq axis (net-new) — sequence/context parallelism: the train step is
    wrapped in jax.shard_map with activations sharded [b, t/seq, f], and
    tracing runs inside `ring.sequence_parallel('seq')` so every
    MultiHeadAttention computes exact ring attention over ICI
    (ops/ring.py) and PositionEmbedding indexes global offsets.
    Gradients/losses are combined with mask-weighted psums, so the result
    equals the single-device step to f32 roundoff even with ragged masks.
    Layers that reduce over time (LSTM, pooling) declare sp_safe=False and
    are refused loudly. COMPOSES with the model axis: the shard_map is
    manual over (data, seq) only (`axis_names`), leaving 'model' to GSPMD,
    so layer-declared tensor shardings keep working inside the
    sequence-parallel step (tp×sp).
  * pipe axis (net-new) — GPipe pipeline parallelism for ANY config-DSL
    layer stack, not just the bespoke ShardedTransformerLM: layers are
    partitioned into contiguous stages balanced by parameter count; each
    device applies ITS stage via lax.switch on the pipe axis index;
    microbatch activations hop stage-to-stage via lax.ppermute as
    flattened max-size-padded carries (heterogeneous boundary shapes —
    conv→flatten→dense — ride one uniform buffer). The autodiff transpose
    of ppermute is the inverse permutation, so backward is the exact
    reverse schedule for free. Stage-replicated params get their partial
    grads completed by a psum over 'pipe'. For deterministic nets the
    gradients equal the single-device full-batch step exactly (GPipe
    microbatching is mathematically a sum split), so loss trajectories
    match to f32 roundoff; stochastic nets (dropout/weight noise) draw
    per-(data-shard, microbatch) keys instead of the single-device
    per-layer split — independent masks, not identical ones.
  * experts over the data axis (net-new) — a layer may keep leaves split
    over ANOTHER axis than the tensor-parallel one and say so itself
    (Layer.partition_specs): `RoutedExperts(exchange_axis="data")` holds
    all its experts spread over the data axis's ranks, [n_experts, ..] as
    ranks x [n_experts / ranks, ..], Adam's moments with them. The
    expert-parallel group IS the data-parallel group (as in GShard): each
    rank routes its own rows, and the layer exchanges tokens with the ranks
    that hold their experts in a `shard_map` island of its own inside the
    GSPMD step (`lax.all_to_all` out and back; nn/layers/hybrid.py). The
    step pins gradients and updated leaves to the declared specs
    (layout.specs_beyond + FsdpArrangement.shard_tree): an expert's
    gradient is complete on its rank and is not reduced over the axis,
    every other gradient rides the all-reduce as before; `model.params`
    stay global arrays, read whole by `device_get` / checkpoints.
Composition: data×model, data×seq, model×seq, and data×pipe are all
supported here; pipe×seq and pipe×model still need the explicit-collective
formulation in parallel/transformer.py (ShardedTransformerLM —
lax.ppermute inside the stage switch does not compose with a GSPMD-managed
model axis: shards reach different collective-permute ids and deadlock, so
those meshes are refused loudly), as do experts over an axis of their own
(`expert`) beside a data axis (ROADMAP R4).
"""
# jaxlint: disable-file=JX018 — batch/carry staging specs (data-axis input
# split, sp/pp plumbing); param placement routes through mesh.py/layout.py

from __future__ import annotations

import functools
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel import layout as layout_mod
from deeplearning4j_tpu.parallel import mesh as mesh_mod
from deeplearning4j_tpu.resilience import chaos
from deeplearning4j_tpu.telemetry import trace as trace_mod
from deeplearning4j_tpu.training import engine as engine_mod
from deeplearning4j_tpu.util import jaxcompat
from deeplearning4j_tpu.datasets.iterators import (
    AsyncDataSetIterator,
    DataSetIterator,
)


class ParallelWrapper:
    """Wraps a MultiLayerNetwork (or ComputationGraph with single in/out) for
    multi-device data(/tensor/sequence)-parallel training.

        pw = ParallelWrapper(net, mesh_spec=MeshSpec(data=8))          # dp
        pw = ParallelWrapper(net, mesh_spec=MeshSpec(data=2, model=4)) # dp×tp
        pw = ParallelWrapper(net, mesh_spec=MeshSpec(data=2, seq=4))   # dp×sp
        pw = ParallelWrapper(net, mesh_spec=MeshSpec(model=2, seq=4))  # tp×sp
        pw = ParallelWrapper(net, mesh_spec=MeshSpec(data=2, pipe=4))  # dp×pp
        pw = ParallelWrapper(net, mesh_spec=MeshSpec(fsdp=4, model=2)) # fsdp×tp
        pw.fit(iterator, epochs=2)

    The wrapped model's params/opt_state are updated in place (sharded); use
    `pw.sync_to_host()` or just keep using `net` — arrays stay addressable.

    An fsdp axis >1 shards params + optimizer state over it (ZeRO-3
    gather-on-use, parallel/layout.py) and attaches the gather hook to the
    wrapped model, which keeps fsdp semantics for later standalone use on
    the same devices; it composes with data/model axes but not with
    seq/pipe (their shard_map bodies pin replicated param specs) or tbptt.
    """

    def __init__(
        self,
        model,
        mesh: Optional[Mesh] = None,
        mesh_spec: Optional[mesh_mod.MeshSpec] = None,
        workers: Optional[int] = None,
        averaging_frequency: int = 1,
        prefetch_buffer: int = 4,
        report_score_after_averaging: bool = True,
        microbatches: Optional[int] = None,
    ):
        self.model = model
        if mesh is None:
            if mesh_spec is None:
                n = workers or len(jax.devices())
                mesh_spec = mesh_mod.MeshSpec(data=n)
            mesh = mesh_mod.build_mesh(mesh_spec)
        self.mesh = mesh
        self.averaging_frequency = max(1, averaging_frequency)
        self.prefetch_buffer = prefetch_buffer
        self.microbatches = microbatches
        self._step = None
        self._param_shardings = None
        self._sp = dict(mesh.shape).get("seq", 1) > 1
        self._pp = dict(mesh.shape).get("pipe", 1) > 1
        self._fsdp_n = dict(mesh.shape).get("fsdp", 1)
        self._tbptt = (getattr(model.conf.defaults, "backprop_type", None)
                       == "tbptt")
        if self._fsdp_n > 1 and (self._sp or self._pp or self._tbptt):
            raise ValueError(
                "fsdp composes with data/model axes only: the seq/pipe "
                "paths run shard_map bodies whose in_specs pin params "
                "replicated (and tbptt threads host carries through "
                "per-chunk steps), so an fsdp-sharded param tree would "
                "be silently gathered per chunk instead of per layer; "
                "use MeshSpec(data=..., fsdp=..., model=...)")
        if self._tbptt and (self._sp or self._pp):
            raise ValueError(
                "truncated BPTT threads RNN carries chunk-by-chunk through "
                "time, which cannot compose with a sharded sequence axis "
                "(chunk-local scans) or pipeline stages (no carry slot in "
                "the microbatch schedule); train tbptt nets under "
                "data/tensor meshes")
        if self._pp and self._sp:
            raise ValueError(
                "pipe x seq factorization is not supported by "
                "ParallelWrapper (the pipeline carry and the ring-attention "
                "hops would need interleaved schedules); use "
                "parallel.transformer.ShardedTransformerLM for pp x sp")
        if self._pp and dict(mesh.shape).get("model", 1) > 1:
            raise ValueError(
                "pipe x model factorization is not supported by "
                "ParallelWrapper: lax.ppermute inside the stage switch "
                "does not compose with a GSPMD-managed model axis (shards "
                "reach different collective-permute ids and deadlock); use "
                "parallel.transformer.ShardedTransformerLM for pp x tp")

    # ------------------------------------------------------------------
    def _check_sp_safe(self, model):
        """Refuse any layer OR graph vertex whose computation crosses the
        time axis (sp_safe=False): under a sharded sequence it would
        silently compute chunk-local results (LSTM scans, pooling,
        LastTimeStep, Reshape across time, input preprocessors)."""
        from deeplearning4j_tpu.nn.graph_vertices import LayerVertex

        def refuse(kind, name):
            raise ValueError(
                f"{kind} {name} reduces/restructures the time axis and "
                f"cannot run with the sequence sharded (sp_safe=False); "
                f"sequence parallelism supports per-timestep and "
                f"ring-aware components only")

        if hasattr(model, "layers"):
            for layer in model.layers:
                if not getattr(layer, "sp_safe", False):
                    refuse("layer", type(layer).__name__)
            if getattr(model.conf, "input_preprocessors", None):
                refuse("input preprocessor", str(sorted(
                    model.conf.input_preprocessors)))
            return
        for name, v in model.conf.vertices.items():
            if isinstance(v, LayerVertex):
                if not getattr(v.layer, "sp_safe", False):
                    refuse("layer", f"{type(v.layer).__name__} ('{name}')")
            elif not getattr(v, "sp_safe", False):
                refuse("vertex", f"{type(v).__name__} ('{name}')")

    @trace_mod.traced("place", category="setup")
    def _place_params(self):
        """Place params with layer-declared tensor-parallel shardings
        (replicates everything when the model axis is 1); updater moments
        mirror their params, everything else replicates. With an fsdp
        axis >1 the layout module composes the fsdp axis onto the
        layer-declared specs and the gather-on-use hook is attached to
        the model BEFORE its train step (re)builds — an already-traced
        step would silently ignore the hook."""
        model, mesh = self.model, self.mesh
        if self._fsdp_n > 1:
            specs = layout_mod.fsdp_param_specs(mesh, model)
            self._fsdp_specs = specs
            self._param_shardings = layout_mod.fsdp_param_shardings(
                mesh, specs)
            model._fsdp_layout = layout_mod.FsdpArrangement(mesh, specs)
            model._train_step = None
            model._train_step_raw = None
        else:
            self._param_shardings = mesh_mod.model_param_shardings(
                mesh, model)
            # a layer that keeps leaves split over another axis than the
            # tensor-parallel one (an expert layer's matrices over its
            # exchange axis): the step pins gradients and updated params to
            # these specs, so the leaves and their moments stay split at rest
            specs = layout_mod.specs_beyond(self._param_shardings, "model")
            if specs is not None:
                model._fsdp_layout = layout_mod.FsdpArrangement(mesh, specs)
                model._train_step = None
                model._train_step_raw = None
        repl = mesh_mod.replicated(mesh)
        model.params = jax.device_put(model.params, self._param_shardings)
        model.state = jax.device_put(model.state, repl)
        if isinstance(model.opt_state, list):  # MultiLayerNetwork
            model.opt_state = [
                jax.device_put(o, mesh_mod.mirror_opt_shardings(
                    mesh, o, self._param_shardings[f"layer_{i}"]))
                for i, o in enumerate(model.opt_state)
            ]
        elif isinstance(model.opt_state, dict):  # ComputationGraph
            model.opt_state = {
                name: jax.device_put(o, mesh_mod.mirror_opt_shardings(
                    mesh, o, self._param_shardings[name]))
                for name, o in model.opt_state.items()
            }
        else:
            model.opt_state = jax.device_put(model.opt_state, repl)

    def _build(self):
        model = self.model
        # placement first: with fsdp it attaches the gather hook and
        # invalidates any pre-built step, so the (re)build below traces
        # the hooked functional core
        self._place_params()
        if model._train_step is None:
            model._train_step = model._build_train_step()

        # ComputationGraph steps take (inputs,), (labels,) tuples;
        # MultiLayerNetwork steps take bare arrays (ParallelWrapper wraps
        # both model kinds, ParallelWrapper.java:59-73)
        from deeplearning4j_tpu.models.computation_graph import (
            ComputationGraph,
        )

        tuple_args = isinstance(model, ComputationGraph)

        def step(params, state, opt_state, iteration, rng, x, y, fm, lm):
            if tuple_args:
                return model._train_step(
                    params, state, opt_state, iteration, rng, (x,), (y,),
                    None if fm is None else (fm,),
                    None if lm is None else (lm,))
            return model._train_step(params, state, opt_state, iteration, rng,
                                     x, y, fm, lm)

        self._step = step

    # ------------------------------------------------------------------
    # sequence-parallel step (shard_map + ring attention)
    # ------------------------------------------------------------------
    def _build_sp(self):
        model = self.model
        mesh = self.mesh
        self._check_sp_safe(model)
        # tp×sp composition: the shard_map below is manual over (data, seq)
        # ONLY (axis_names); the 'model' axis stays in GSPMD's hands, so the
        # layer-declared tensor shardings placed here propagate through the
        # sequence-parallel body exactly as they do in the jit path.
        self._place_params()
        from deeplearning4j_tpu.models.computation_graph import (
            ComputationGraph,
        )
        from deeplearning4j_tpu.nn.layers import base as base_mod
        from deeplearning4j_tpu.ops import ring

        tuple_args = isinstance(model, ComputationGraph)
        d_ax, s_ax = "data", "seq"

        def loss_adapter(params, state, x, y, rng, fm, lm):
            if tuple_args:
                s, (new_state, _) = model._loss(
                    params, state, (x,), (y,), rng, (fm,), (lm,))
            else:
                s, new_state = model._loss(params, state, x, y, rng, fm, lm)
            return s, new_state

        n_seq = dict(mesh.shape)["seq"]
        n_shards = dict(mesh.shape)["data"] * n_seq

        def local_grads(params, state, x, y, rng, fm, lm):
            # per-shard independent randomness: a replicated key would draw
            # IDENTICAL dropout masks on every data/seq shard (positions t
            # and t + t_loc always dropped together). Deterministic nets
            # reproduce the single-device step exactly; stochastic nets
            # get independent per-shard draws instead of correlated ones.
            rng = jax.random.fold_in(
                rng, lax.axis_index(d_ax) * n_seq + lax.axis_index(s_ax))
            # this shard's weight in the global mean: active loss slots
            # (the loss normalizes by sum(mask) — losses.compute); with no
            # mask anywhere, shards are equal-sized so the weight is the
            # static 1/n_shards. The psum'd total is computed OUTSIDE the
            # grad so no cross-shard collective is differentiated
            # (transformer.py's policy).
            wmask = lm if lm is not None else fm
            if wmask is None:
                wt = 1.0 / n_shards
            else:
                w = jnp.sum(wmask)
                wt = w / jnp.maximum(lax.psum(w, (d_ax, s_ax)), 1.0)

            # The weight multiplies the loss BEFORE differentiation. Ring
            # attention's backward sends cotangents ACROSS shards (the
            # ppermute transpose), so a shard's computed grad mixes
            # contributions from every shard's loss; scaling grads after
            # the fact would re-weight those cross-shard flows with the
            # wrong shard's weight (only uniform weights would survive
            # it). Seeding each shard's backward with its own weight makes
            # every cotangent carry the right factor wherever it lands;
            # the plain psum then reproduces the global mask-weighted
            # gradient exactly. Σ wt = 1, so the (shard-identical)
            # regularization terms pass through with weight exactly 1.
            def weighted_loss(p):
                s, ns = loss_adapter(p, state, x, y, rng, fm, lm)
                return s * wt, ns

            with ring.sequence_parallel(s_ax):
                (score_w, new_state), grads = jax.value_and_grad(
                    weighted_loss, has_aux=True)(params)
            grads = jax.tree_util.tree_map(
                lambda g: lax.psum(g, (d_ax, s_ax)), grads)
            score = lax.psum(score_w, (d_ax, s_ax))
            new_state = jax.tree_util.tree_map(
                lambda s_: (lax.pmean(s_, (d_ax, s_ax))
                            if jnp.issubdtype(jnp.asarray(s_).dtype,
                                              jnp.inexact) else s_),
                new_state)
            return grads, new_state, score

        def make_step(x_ndim, y_ndim, has_fm, has_lm):
            # None masks stay None through the forward: a materialized
            # all-ones mask would force every ring hop to ppermute a mask
            # over ICI and take the masked-score path — pure overhead on
            # the mask-free hot path (the common LM case)
            x_spec = P(d_ax, s_ax, *([None] * (x_ndim - 2)))
            y_spec = P(d_ax, s_ax, *([None] * (y_ndim - 2)))
            m_spec = P(d_ax, s_ax)
            smapped = jax.shard_map(
                local_grads, mesh=mesh,
                in_specs=(P(), P(), x_spec, y_spec, P(),
                          m_spec if has_fm else P(),
                          m_spec if has_lm else P()),
                out_specs=(P(), P(), P()),
                axis_names={d_ax, s_ax},
                check_vma=False)

            def step(params, state, opt_state, iteration, rng, x, y, fm, lm):
                with base_mod.iteration_scope(iteration):
                    grads, new_state, score = smapped(params, state, x, y,
                                                      rng, fm, lm)
                new_params, new_opt = model._apply_updates(
                    params, grads, opt_state, iteration)
                return new_params, new_state, new_opt, score

            return jaxcompat.jit(step, donate_argnums=(0, 1, 2),
                                 watch_name="ParallelWrapper.sp_step")

        cache = {}

        def step(params, state, opt_state, iteration, rng, x, y, fm, lm):
            key = (x.ndim, y.ndim, fm is not None, lm is not None)
            if key not in cache:
                cache[key] = make_step(*key)
            return cache[key](params, state, opt_state, iteration, rng,
                              x, y, fm, lm)

        self._step = step

    # ------------------------------------------------------------------
    # pipeline-parallel step (lax.switch stages + ppermute microbatches)
    # ------------------------------------------------------------------
    def _check_pp_model(self):
        """Refusals specific to the pipeline axis — every one loud, never a
        silent semantic change (the sp_safe policy applied to pp)."""
        model = self.model
        if not hasattr(model, "layers"):
            raise ValueError(
                "pipeline parallelism needs a sequential layer stack "
                "(MultiLayerNetwork); DAG ComputationGraphs have no single "
                "stage cut — train them under data/tensor/sequence axes")
        from deeplearning4j_tpu.nn.layers.output import BaseOutputLayer

        if not isinstance(model.layers[-1], BaseOutputLayer):
            raise ValueError(
                "pipeline parallelism requires a loss-bearing final layer")
        if jax.tree_util.tree_leaves(model.state):
            raise ValueError(
                "pipeline parallelism cannot thread running state (e.g. "
                "BatchNorm statistics) through microbatched stages; train "
                "stateful nets under data/tensor parallelism instead")
        pp = dict(self.mesh.shape)["pipe"]
        if len(model.layers) - 1 < pp:
            raise ValueError(
                f"{len(model.layers) - 1} pipelineable layers cannot fill "
                f"pipe={pp} stages")

    def _pp_stage_bounds(self, pp: int):
        """Contiguous [lo, hi) layer ranges per stage, balanced by param
        count (the FLOPs proxy), always leaving >=1 layer per remaining
        stage. The final output layer stays OUTSIDE the pipeline: its loss
        is computed post-pipeline on every pipe device and masked to the
        last stage (the ShardedTransformerLM logits policy generalized)."""
        model = self.model
        n = len(model.layers) - 1
        sizes = [1 + sum(x.size for x in jax.tree_util.tree_leaves(
            model.params[f"layer_{i}"])) for i in range(n)]
        bounds = []
        lo = 0
        remaining = float(sum(sizes))
        for s in range(pp):
            rem = pp - s - 1
            if rem == 0:
                bounds.append((lo, n))
                break
            target = remaining / (rem + 1)
            hi = lo + 1
            acc = float(sizes[lo])
            while (hi < n - rem
                   and abs(acc + sizes[hi] - target) <= abs(target - acc)):
                acc += sizes[hi]
                hi += 1
            bounds.append((lo, hi))
            remaining -= acc
            lo = hi
        return bounds

    def _build_pp(self):
        self._check_pp_model()
        self._place_params()
        model, mesh = self.model, self.mesh
        pp = dict(mesh.shape)["pipe"]
        n_data = dict(mesh.shape)["data"]
        layers = model.layers
        n_pipelined = len(layers) - 1
        bounds = self._pp_stage_bounds(pp)
        from deeplearning4j_tpu.nn import weightnoise as wn_mod
        from deeplearning4j_tpu.nn.layers import base as base_mod

        preprocs = model.conf.input_preprocessors
        state0 = model.state  # empty per-layer dicts (checked above)
        k_out = f"layer_{len(layers) - 1}"
        out_layer = layers[-1]

        def seg_forward(params, x, lo, hi, rngs):
            """Layers [lo, hi) — the stateless slice of
            MultiLayerNetwork._forward (state and feature masks refused)."""
            for i in range(lo, hi):
                layer = layers[i]
                if i in preprocs:
                    x = preprocs[i].transform(x, None)
                k = f"layer_{i}"
                p_i = wn_mod.maybe_transform(layer, params[k], rngs[i], True)
                x, _ = layer.apply(p_i, x, state=state0[k], train=True,
                                   rng=rngs[i], mask=None)
            return x

        def make_step(x_sh, x_dt, y_sh, has_lm):
            if x_sh[0] % n_data:
                raise ValueError(f"batch {x_sh[0]} must divide data axis "
                                 f"{n_data}")
            b_loc = x_sh[0] // n_data
            if self.microbatches:
                M = self.microbatches
                if b_loc % M:
                    raise ValueError(
                        f"per-data-shard batch {b_loc} must divide into "
                        f"microbatches={M} (pad the iterator or change "
                        f"ParallelWrapper(microbatches=...))")
            else:
                # largest divisor of the local batch <= pp (GPipe is exact
                # for ANY M >= 1; fewer microbatches only grow the bubble)
                M = next(m for m in range(min(pp, b_loc), 0, -1)
                         if b_loc % m == 0)
            bm = b_loc // M
            feat_in = tuple(x_sh[1:])
            keys0 = jax.random.split(jax.random.PRNGKey(0), len(layers))

            # activation shape/dtype at each stage boundary, via abstract
            # tracing of the prefix forward (heterogeneous nets: conv ->
            # flatten -> dense all welcome; the carry is a flat max-size
            # padded buffer)
            shape_at = {0: jax.ShapeDtypeStruct((bm,) + feat_in, x_dt)}
            for idx in sorted({hi for _, hi in bounds} | {lo for lo, _ in bounds}):
                if idx == 0:
                    continue
                shape_at[idx] = jax.eval_shape(
                    lambda p, xx, r, idx=idx: seg_forward(p, xx, 0, idx, r),
                    model.params, shape_at[0], keys0)
            out_sd = shape_at[n_pipelined]
            out_nflat = int(np.prod(out_sd.shape[1:]))
            flat_of = {s: int(np.prod(shape_at[hi].shape[1:]))
                       for s, (_, hi) in enumerate(bounds)}
            maxflat = max(flat_of.values())
            carry_dt = jnp.result_type(
                *[shape_at[hi].dtype for _, hi in bounds])

            def pipeline_forward(params, x_loc, rng):
                """GPipe over heterogeneous stages: M microbatches, pp
                stages, M+pp-1 steps; each device runs ITS stage via
                lax.switch on the pipe index; stage outputs hop as padded
                flat buffers via ppermute, whose autodiff transpose gives
                the exact reverse schedule (parallel/transformer.py:346
                generalized to any config-DSL layer list)."""
                x_mb = x_loc.reshape((M, bm) + feat_in)
                stage = lax.axis_index("pipe")
                fwd_perm = [(i, i + 1) for i in range(pp - 1)]
                outputs = jnp.zeros((M,) + out_sd.shape, out_sd.dtype)
                carry = jnp.zeros((bm, maxflat), carry_dt)

                def branch_fn(s, carry, mb, rngs):
                    lo, hi = bounds[s]
                    if s == 0:
                        x = mb
                    else:
                        ish = shape_at[lo]
                        nfl = int(np.prod(ish.shape[1:]))
                        x = carry[:, :nfl].reshape(ish.shape).astype(
                            ish.dtype)
                    x = seg_forward(params, x, lo, hi, rngs)
                    flat = x.astype(carry_dt).reshape(bm, -1)
                    if flat.shape[1] < maxflat:
                        flat = jnp.pad(
                            flat, ((0, 0), (0, maxflat - flat.shape[1])))
                    return flat

                branches = [lambda c, m, r, s=s: branch_fn(s, c, m, r)
                            for s in range(pp)]
                for t in range(M + pp - 1):
                    mb = x_mb[min(t, M - 1)]
                    # the microbatch THIS stage processes at schedule slot t
                    # keys its dropout/weight-noise draws, so each
                    # microbatch sees one consistent mask per layer
                    mb_here = jnp.clip(t - stage, 0, M - 1)
                    rngs = jax.random.split(
                        jax.random.fold_in(rng, mb_here), len(layers))
                    out = lax.switch(stage, branches, carry, mb, rngs)
                    out_idx = t - (pp - 1)
                    if out_idx >= 0:
                        res = out[:, :out_nflat].reshape(out_sd.shape)
                        res = res.astype(out_sd.dtype)
                        outputs = outputs.at[out_idx].set(
                            jnp.where(stage == pp - 1, res,
                                      outputs[out_idx]))
                    if t != M + pp - 2:
                        carry = lax.ppermute(out, "pipe", fwd_perm)
                return outputs.reshape((b_loc,) + out_sd.shape[1:])

            def local_grads(params, x, y, lm, rng):
                # per-data-shard randomness: a replicated key would draw
                # IDENTICAL dropout/weight-noise masks on every data shard
                # (the correlated-draw hazard the sp path documents);
                # pipe devices of one data shard share the key — each
                # layer runs on exactly one stage, so draws stay
                # per-(shard, microbatch) consistent
                rng = jax.random.fold_in(rng, lax.axis_index("data"))
                # local share of the global active-slot count: computed
                # OUTSIDE the grad so no cross-shard psum is differentiated
                # (parallel/transformer.py gradient-correctness policy)
                if has_lm:
                    wloc = jnp.sum(lm)
                    wt = wloc / jnp.maximum(lax.psum(wloc, "data"), 1.0)
                else:
                    wt = 1.0 / n_data

                def weighted_loss(p):
                    h = pipeline_forward(p, x, rng)
                    p_out = wn_mod.maybe_transform(out_layer, p[k_out], rng,
                                                   True)
                    score, _, _ = out_layer.compute_loss(
                        p_out, h, y, state=state0[k_out], mask=lm, rng=rng)
                    score = (score + model._reg_score(p)) * wt
                    # exactly one cotangent seed enters the pipeline (the
                    # last stage); transposed ppermutes carry it back
                    # through every stage
                    return jnp.where(lax.axis_index("pipe") == pp - 1,
                                     score, 0.0)

                score_w, grads = jax.value_and_grad(weighted_loss)(params)
                # stage-owned grads are nonzero on their stage only; the
                # pipe psum completes them (and data-averages ride along)
                grads = jax.tree_util.tree_map(
                    lambda g: lax.psum(g, ("data", "pipe")), grads)
                return grads, lax.psum(score_w, ("data", "pipe"))

            x_spec = P("data", *([None] * (len(x_sh) - 1)))
            y_spec = P("data", *([None] * (len(y_sh) - 1)))
            smapped = jax.shard_map(
                local_grads, mesh=mesh,
                in_specs=(P(), x_spec, y_spec,
                          P("data") if has_lm else P(), P()),
                out_specs=(P(), P()),
                axis_names={"data", "pipe"}, check_vma=False)

            def step(params, state, opt_state, iteration, rng, x, y, lm):
                with base_mod.iteration_scope(iteration):
                    grads, score = smapped(params, x, y, lm, rng)
                new_params, new_opt = model._apply_updates(
                    params, grads, opt_state, iteration)
                return new_params, state, new_opt, score

            return jaxcompat.jit(step, donate_argnums=(0, 2),
                                 watch_name="ParallelWrapper.pp_step")

        cache = {}

        def step(params, state, opt_state, iteration, rng, x, y, fm, lm):
            if fm is not None:
                raise ValueError(
                    "pipeline parallelism does not thread feature masks "
                    "through stages; use data/tensor/sequence axes for "
                    "masked-input nets")
            key = (tuple(x.shape), str(x.dtype), tuple(y.shape),
                   lm is not None)
            if key not in cache:
                cache[key] = make_step(tuple(x.shape), x.dtype,
                                       tuple(y.shape), lm is not None)
            return cache[key](params, state, opt_state, iteration, rng,
                              x, y, lm)

        self._step = step

    # ------------------------------------------------------------------
    # truncated BPTT under data(/tensor) parallelism
    # ------------------------------------------------------------------
    def _fit_tbptt_batch(self, ds, unpadded: int):
        """One batch of the reference's ParallelWrapper-over-tBPTT-net
        case (ParallelWrapper.java wraps any Model; the fit loop defers
        to MultiLayerNetwork.doTruncatedBPTT): the model's OWN chunk
        loop and jitted step run unmodified — the only wrapper delta is
        the `put` placement hook sharding the batch axis (inputs, masks,
        and the RNN carries) over 'data', so GSPMD turns the per-chunk
        gradient reduction into the dp psum and the trajectory equals
        single-device model.fit() chunk for chunk. Tensor-axis shardings
        placed by _place_params propagate through the same step
        (dp x tp)."""
        model, mesh = self.model, self.mesh
        from deeplearning4j_tpu.models.computation_graph import (
            ComputationGraph,
        )

        # same env-gated chaos site as _fit_std_batch: the tbptt path is a
        # multi-device step too, and its recovery arc must be provable
        chaos.fault_point("collective")
        put = functools.partial(_put, mesh)
        if isinstance(model, ComputationGraph):
            from deeplearning4j_tpu.datasets.dataset import MultiDataSet

            model._fit_tbptt(MultiDataSet.from_dataset(ds), put=put,
                             report_batch=unpadded)
        else:
            model._fit_tbptt(ds, put=put, report_batch=unpadded)

    # ------------------------------------------------------------------
    def _fit_std_batch(self, ds, unpadded: int):
        """One (already padded) batch through the built standard step."""
        model, mesh = self.model, self.mesh
        n_seq = dict(mesh.shape).get("seq", 1)
        if self._sp:
            t = ds.features.shape[1]
            if t % n_seq != 0:
                raise ValueError(
                    f"sequence length {t} must divide by the seq "
                    f"axis ({n_seq}); bucket or pad the iterator "
                    f"(BucketSequenceIterator) to a multiple")
        # the phases of the engine's `step` span (docs/TELEMETRY.md); the
        # dp(/tp) step is staged and dispatched by the engine loop itself,
        # this is the whole step of an sp/pp mesh (and of a tbptt
        # wrapper's batch that cannot be time-sliced)
        tr = trace_mod.tracer()
        with tr.span("put", category="collective",
                     bytes=engine_mod.host_nbytes(ds)):
            args = self._stage_std(ds, seq=self._sp)
        # env-gated chaos site for the multi-device step: a "preempted
        # collective" surfaces here as ChaosError out of fit(), which a
        # CheckpointManager-resumed rerun must survive (tier-1 proven)
        chaos.fault_point("collective")
        with tr.span("dispatch", category="collective"):
            score = self._dispatch_std(args)
        engine_mod.finish_step(tr, model, score, unpadded)

    def _stage_std(self, ds, seq: bool = False):
        """The (already padded) batch handed to the runtime with the
        sharding the standard step uses: `(x, y, fm, lm)`."""
        mesh = self.mesh
        return tuple(_put(mesh, a, seq=seq)
                     for a in (ds.features, ds.labels, ds.features_mask,
                               ds.labels_mask))

    def _dispatch_std(self, args):
        """One built standard step on staged `(x, y, fm, lm)`, traced
        under the ambient mesh."""
        return engine_mod.dispatch_step(self.model, self._step, args,
                                        self._step_scope)

    def _step_scope(self):
        """Entered around every jitted standard-step call (per-step and
        windowed): the mesh is AMBIENT for what the call traces, and Pallas
        kernel call sites read it to run per batch shard (ops/kernel_call.py
        per_batch_shard) — GSPMD cannot partition their custom calls.
        The scope holds the jitted call only: eager work under an ambient
        mesh is placed on that mesh, which would strand host-side state
        (the rng key) on a mesh the elastic masters later shrink or
        regrow. The tbptt chunk loop interleaves eager carry handling with
        its jitted chunk steps and is not covered: a kernel forced on
        there fails to lower on a TPU mesh, loudly."""
        return jax.set_mesh(self.mesh)

    def _ensure_std_step(self):
        if self._step is None:
            if self._pp:
                self._build_pp()
            elif self._sp:
                self._build_sp()
            else:
                self._build()

    def _raw_window_step(self):
        """The wrapped model's raw (unjitted) train step with the
        ComputationGraph tuple adaptation — what the window engine scans
        for the standard dp(/tp) path. None (windowing off) for sp/pp/
        tbptt meshes, whose steps keep per-step dispatch. Memoized per
        underlying raw step: the engine's scan cache is keyed on step
        identity, so a fresh adapter closure per fit() would recompile
        the window program every fit."""
        if self._sp or self._pp or self._tbptt:
            return None
        raw = getattr(self.model, "_train_step_raw", None)
        if raw is None:
            return None
        cached = getattr(self, "_window_raw", None)
        if cached is not None and self._window_raw_src is raw:
            return cached
        from deeplearning4j_tpu.models.computation_graph import (
            ComputationGraph,
        )

        if not isinstance(self.model, ComputationGraph):
            step = raw
        else:
            def step(params, state, opt_state, iteration, rng, x, y, fm,
                     lm):
                return raw(params, state, opt_state, iteration, rng,
                           (x,), (y,),
                           None if fm is None else (fm,),
                           None if lm is None else (lm,))

        self._window_raw = step
        self._window_raw_src = raw
        return step

    def fit(self, iterator: DataSetIterator, epochs: int = 1,
            **attachments):
        """The outer fit lifecycle — resume/save cadence, stall-watchdog
        heartbeats (a hung collective in the SPMD step is exactly what
        the watchdog exists to catch — docs/HEALTH.md), listener firing
        order, crash-path flight bundles — is engine-owned
        (training/engine.py TrainingRun); `**attachments` forwards the
        resilience manager keyword there unchanged. The run restores the
        WRAPPED model BEFORE params are placed on the mesh, and `epochs`
        stays the TOTAL target (docs/RESILIENCE.md)."""
        model = self.model
        run = engine_mod.TrainingRun(model, "ParallelWrapper.fit",
                                     epochs=epochs, **attachments)
        if self._tbptt:
            if self._param_shardings is None:
                self._place_params()
        else:
            self._ensure_std_step()
        mesh = self.mesh
        own_async = None
        if (iterator is not None and isinstance(iterator, DataSetIterator)
                and not isinstance(iterator, AsyncDataSetIterator)
                and iterator.async_supported()):
            iterator = own_async = AsyncDataSetIterator(
                iterator, self.prefetch_buffer)
        n_data = dict(mesh.shape)["data"]

        def prep(ds):
            b = ds.features.shape[0]
            if b % n_data != 0:
                # pad the tail batch to a multiple of the data axis
                ds = _pad_batch(ds, n_data - b % n_data)
            return ds, b

        def exec_one(ds):
            ds, b = prep(ds)
            if (self._tbptt and ds.features.ndim == 3
                    and ds.labels.ndim == 3):
                self._fit_tbptt_batch(ds, unpadded=b)
            else:
                if self._tbptt:
                    # per-sequence (2D) labels can't be time-sliced:
                    # standard full-BPTT step, the same fallback the
                    # models apply for non-3D labels
                    self._ensure_std_step()
                self._fit_std_batch(ds, unpadded=b)

        def stage(ds):
            # the standard dp(/tp) SPMD step is staged (one batch ahead
            # per step, K at a time windowed); tbptt chunk loops and the
            # shape-keyed sp/pp step caches keep their own per-step
            # dispatch (docs/PERFORMANCE.md)
            if self._tbptt or self._sp or self._pp:
                return None
            ds, b = prep(ds)
            return self._stage_std(ds), b

        def place_window(window):
            # window axis leads: batch axis moves to position 1, sharded
            # over 'data' as in the per-step path
            def put_w(a):
                sh = NamedSharding(mesh, P(None, "data",
                                           *([None] * (a.ndim - 2))))
                return jax.device_put(a, sh)

            return jax.tree_util.tree_map(put_w, window)

        loop = engine_mod.WindowedFitLoop(
            model, raw_step=self._raw_window_step(),
            stage=stage, dispatch=self._dispatch_std, exec_one=exec_one,
            # the same env-gated chaos site as _fit_std_batch, once per
            # dispatch of staged args (a step, or a window)
            on_dispatch=lambda: chaos.fault_point("collective"),
            dispatch_scope=self._step_scope,
            place_window=place_window, span_category="collective",
            watch_prefix="ParallelWrapper")
        # on a crash the prefetch producer thread we started would
        # otherwise spin forever on its full queue (and pin device-
        # resident batches) — the elastic masters retry a failed split in
        # a loop, so one leak per eviction compounds (shutdown is
        # idempotent and reset-safe; a SUCCESSFUL fit leaves the iterator
        # live for reuse, matching historical behavior)
        return run.execute(
            loop, iterator,
            cleanup_on_crash=(own_async.shutdown
                              if own_async is not None else None))

    def sync_to_host(self):
        """Gather params to host (e.g. before serialization)."""
        self.model.params = jax.device_get(self.model.params)
        return self.model

    # reference-API aliases
    def shutdown(self):
        pass

    def stop_fit(self):
        pass


def _put(mesh, arr, seq: bool = False):
    if arr is None:
        return None
    # device arrays pass straight to device_put — np.asarray would
    # round-trip through host
    x = arr if isinstance(arr, jax.Array) else np.asarray(arr)
    if seq and x.ndim >= 2:
        sh = NamedSharding(mesh, P("data", "seq", *([None] * (x.ndim - 2))))
    else:
        sh = NamedSharding(mesh, P("data", *([None] * (x.ndim - 1))))
    return jax.device_put(x, sh)


def _pad_batch(ds, pad):
    from deeplearning4j_tpu.datasets.dataset import DataSet

    def padded(a):
        if a is None:
            return None
        reps = np.concatenate([a, np.repeat(a[-1:], pad, axis=0)], axis=0)
        return reps

    # padded rows masked out of the loss when a labels mask exists; otherwise
    # they contribute duplicated examples (same as reference's last-batch
    # handling under round-robin dispatch)
    fm = padded(ds.features_mask)
    lm = padded(ds.labels_mask)
    if lm is not None:
        lm[-pad:] = 0.0
    return DataSet(padded(ds.features), padded(ds.labels), fm, lm)
