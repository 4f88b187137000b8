"""ComputationGraph — DAG network runtime.

Reference: nn/graph/ComputationGraph.java:3360 — fit(MultiDataSet):977,
output:1529/1553, calcBackpropGradients:1626 (reverse topological order),
rnnTimeStep:2359.

TPU-native: the topological order is computed once from the config; the whole
forward DAG traces into ONE jitted XLA program (SURVEY.md §7: 'topo order is
free — trace the config into one jitted fn'), and jax.grad differentiates the
DAG — there is no reverse-topological backward pass to write. Training step
donates params/opt-state as in MultiLayerNetwork.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.datasets.iterators import (
    AsyncDataSetIterator,
    DataSetIterator,
    ListDataSetIterator,
)
from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.training import engine as engine_mod
from deeplearning4j_tpu.util import jaxcompat
from deeplearning4j_tpu.nn import weightnoise as wn_mod
from deeplearning4j_tpu.nn import updaters as upd_mod
from deeplearning4j_tpu.nn.graph_conf import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.graph_vertices import LayerVertex
from deeplearning4j_tpu.nn.layers import base as base_mod
from deeplearning4j_tpu.nn.layers.output import BaseOutputLayer
from deeplearning4j_tpu.nn.regularization import apply_constraints
from deeplearning4j_tpu.telemetry import introspect as introspect_mod
from deeplearning4j_tpu.telemetry.trace import device_scope

PyTree = Any


class ComputationGraph:
    def __init__(self, conf: ComputationGraphConfiguration):
        conf.validate()
        self.conf = conf
        self.topo = conf.topological_order()
        self.vertex_types = conf.vertex_output_types()
        self.params: Optional[Dict[str, PyTree]] = None
        self.state: Optional[Dict[str, PyTree]] = None
        self.opt_state: Optional[Dict[str, PyTree]] = None
        self.iteration = 0
        self.epoch = 0
        self.listeners: List = []
        self.score_ = float("nan")
        self.last_batch_size = 0
        self.last_etl_time_ms = 0.0
        self._rng = jax.random.PRNGKey(conf.defaults.seed)
        self._train_step = None
        self._output_fn = None
        self._updaters = self._resolve_updaters()
        self._vin_types = {name: self._in_types(name) for name in self.topo}

    def _vertex_input_types(self, name):
        return [self.vertex_types[i] if i in self.vertex_types else None
                for i in self.conf.vertex_inputs[name]]

    def _in_types(self, name):
        types = {}
        if self.conf.input_types:
            for n, t in zip(self.conf.network_inputs, self.conf.input_types):
                types[n] = t
        types.update(self.vertex_types)
        return [types[i] for i in self.conf.vertex_inputs[name]]

    def _resolve_updaters(self):
        out = {}
        for name, v in self.conf.vertices.items():
            layer = v.layer if isinstance(v, LayerVertex) else None
            u = None
            if layer is not None and layer.updater is not None:
                u = layer.updater
            u = upd_mod.get(u if u is not None else self.conf.defaults.updater)
            if layer is not None and layer.learning_rate is not None:
                import copy

                u = copy.copy(u)
                u.learning_rate = layer.learning_rate
            out[name] = u
        return out

    @introspect_mod.init_span()
    def init(self) -> "ComputationGraph":
        key = jax.random.PRNGKey(self.conf.defaults.seed)
        keys = jax.random.split(key, max(len(self.topo), 1))
        self.params, self.state = {}, {}
        for i, name in enumerate(self.topo):
            v = self.conf.vertices[name]
            in_types = self._in_types(name)
            self.params[name] = (v.init_params(keys[i], in_types)
                                 if v.has_params() else {})
            self.state[name] = v.init_state(in_types)
        self.opt_state = {
            name: self._updaters[name].init_state(self.params[name])
            for name in self.topo
        }
        return self

    def num_params(self) -> int:
        return int(sum(l.size for l in jax.tree_util.tree_leaves(self.params)))

    def set_listeners(self, *ls):
        self.listeners = list(ls)
        return self

    def feed_forward(self, *inputs, train: bool = False):
        """Input + vertex activations in topological order
        (ComputationGraph.feedForward's activations map; inputs lead, as in
        MultiLayerNetwork.feed_forward). Always inference-mode activations —
        the `train` kwarg exists for API compatibility and is ignored, like
        the MLN counterpart (stochastic train-mode activations without an
        rng would be a hybrid neither path produces)."""
        del train
        arrs = tuple(jnp.asarray(x) for x in inputs)
        acts, _, _, _ = self._forward(self.params, self.state, arrs,
                                      train=False, rng=None,
                                      stop_at_outputs=False)
        return ([np.asarray(a) for a in arrs]
                + [np.asarray(acts[name]) for name in self.topo])

    def summary(self) -> str:
        """Architecture table (ComputationGraph.summary())."""
        lines = ["=" * 78]
        lines.append(f"{'vertex':<22}{'type':<24}{'out shape':<20}"
                     f"{'params':>10}")
        lines.append("-" * 78)
        for name in self.conf.network_inputs:
            t = self.vertex_types.get(name)
            shape = str(t.shape()) if t is not None else ""
            lines.append(f"{name:<22}{'Input':<24}{shape:<20}{0:>10}")
        for name in self.topo:
            v = self.conf.vertices[name]
            kind = (type(v.layer).__name__
                    if isinstance(v, LayerVertex) else type(v).__name__)
            t = self.vertex_types.get(name)
            shape = str(t.shape()) if t is not None else ""
            n = (sum(x.size for x in
                     jax.tree_util.tree_leaves(self.params[name]))
                 if self.params else 0)
            lines.append(f"{name:<22}{kind:<24}{shape:<20}{n:>10}")
        lines.append("-" * 78)
        lines.append(f"total params: {self.num_params() if self.params else 0}")
        lines.append("=" * 78)
        return "\n".join(lines)

    # ------------------------------------------------------------------
    # functional core
    # ------------------------------------------------------------------
    def _forward(self, params, state, inputs: Sequence[jnp.ndarray], *,
                 train: bool, rng, masks: Optional[Sequence] = None,
                 stop_at_outputs: bool = True, carries=None):
        """`carries` (dict vertex-name -> recurrent carry) enables stateful
        RNN eval/tBPTT through the DAG (ComputationGraph.rnnTimeStep:2359);
        returns (acts, new_state, mask_map, new_carries)."""
        from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrent

        acts: Dict[str, jnp.ndarray] = dict(zip(self.conf.network_inputs, inputs))
        mask_map: Dict[str, Optional[jnp.ndarray]] = dict(
            zip(self.conf.network_inputs, masks or [None] * len(inputs))
        )
        new_state = dict(state)
        new_carries = dict(carries) if carries is not None else None
        rngs = (jax.random.split(rng, len(self.topo))
                if rng is not None else [None] * len(self.topo))
        out_set = set(self.conf.network_outputs)
        # fsdp gather-on-use hook (parallel/layout.py, attached by
        # ParallelWrapper when the mesh's fsdp axis is >1): each vertex's
        # subtree is gathered right before use, inside its remat scope
        fsdp = getattr(self, "_fsdp_layout", None)
        for i, name in enumerate(self.topo):
            v = self.conf.vertices[name]
            vin = [acts[x] for x in self.conf.vertex_inputs[name]]
            vmasks = [mask_map.get(x) for x in self.conf.vertex_inputs[name]]
            if stop_at_outputs and name in out_set and isinstance(v, LayerVertex) \
                    and isinstance(v.layer, BaseOutputLayer):
                # leave pre-output activation for the loss fn
                acts[name] = vin[0] if len(vin) == 1 else vin
                mask_map[name] = vmasks[0] if vmasks else None
                continue
            # the vertex's device scope (telemetry/trace.py), opened
            # INSIDE what remat wraps: the recompute carries it too
            kind = type(v.layer if isinstance(v, LayerVertex) else v).__name__
            if (new_carries is not None and isinstance(v, LayerVertex)
                    and isinstance(v.layer, BaseRecurrent)):
                with device_scope(kind=kind, layer=name):
                    p = (params[name] if fsdp is None
                         else fsdp.gather(name, params[name]))
                    p = wn_mod.maybe_transform(v.layer, p, rngs[i], train)
                    y, c_out = v.layer.scan(
                        p, vin[0], new_carries[name],
                        mask=vmasks[0] if vmasks else None,
                        train=train, rng=rngs[i])
                new_carries[name] = c_out
            else:
                def run(p_raw, xin, st, r, ms, _v=v, _name=name, _kind=kind):
                    with device_scope(kind=_kind, layer=_name):
                        p_g = (p_raw if fsdp is None
                               else fsdp.gather(_name, p_raw))
                        return _v.apply(p_g, xin, state=st, train=train,
                                        rng=r, masks=ms)

                layer = v.layer if isinstance(v, LayerVertex) else None
                pol = getattr(layer, "remat", None) if layer else None
                if train and pol:
                    # local import: parallel/__init__ pulls in wrapper,
                    # which reaches back into models at import time
                    from deeplearning4j_tpu.parallel import (
                        layout as layout_mod,
                    )

                    run = layout_mod.maybe_remat(run, pol)
                y, s = run(params[name], vin, state[name], rngs[i], vmasks)
                if train:
                    new_state[name] = s
            acts[name] = y
            mask_map[name] = v.propagate_mask(vmasks, self._vin_types[name])
        return acts, new_state, mask_map, new_carries

    def _reg_score(self, params):
        total = jnp.zeros(())
        d = self.conf.defaults
        for name, v in self.conf.vertices.items():
            if not isinstance(v, LayerVertex) or not params[name]:
                continue
            layer = v.layer
            p = params[name]
            l1 = layer.l1 if layer.l1 is not None else d.l1
            l2 = layer.l2 if layer.l2 is not None else d.l2
            if l1 or l2:
                for val in jax.tree_util.tree_leaves(layer.regularizable(p)):
                    if l1:
                        total = total + l1 * jnp.sum(jnp.abs(val))
                    if l2:
                        total = total + 0.5 * l2 * jnp.sum(val * val)
        return total

    def _loss(self, params, state, inputs, labels, rng, fmasks, lmasks,
              train=True, carries=None):
        acts, new_state, mask_map, new_carries = self._forward(
            params, state, inputs, train=train, rng=rng, masks=fmasks,
            carries=carries
        )
        with device_scope(kind="loss"):  # heads, losses and the penalty
            total = jnp.zeros(())
            for oi, oname in enumerate(self.conf.network_outputs):
                v = self.conf.vertices[oname]
                assert isinstance(v, LayerVertex) and isinstance(v.layer, BaseOutputLayer), (
                    f"output vertex '{oname}' must wrap an output layer"
                )
                x_in = acts[oname]
                lmask = None
                if lmasks is not None:
                    lmask = lmasks[oi]
                if lmask is None:
                    lmask = mask_map.get(oname)
                fsdp = getattr(self, "_fsdp_layout", None)
                p_out = (params[oname] if fsdp is None
                         else fsdp.gather(oname, params[oname]))
                p_out = wn_mod.maybe_transform(v.layer, p_out, rng, train)
                score, per_ex, out_state = v.layer.compute_loss(
                    p_out, x_in, labels[oi], state=state[oname],
                    mask=lmask, rng=rng,
                )
                new_state[oname] = out_state
                total = total + score
            total = total + self._reg_score(params)
        return total, (new_state, new_carries)

    def _check_policy(self):
        """Invalidate cached jitted fns when the global precision policy
        changed since they were traced (dtypes.policy_fingerprint)."""
        fp = dtypes.policy_fingerprint()
        if getattr(self, "_policy_fp", None) != fp:
            self._policy_fp = fp
            self._train_step = None
            self._output_fn = None
            self._tbptt_step = None


    def _apply_updates(self, params, grads, opt_state, iteration):
        """Per-vertex gradient-normalization + updater + constraints —
        shared by the standard and tBPTT train steps."""
        with device_scope(kind="update"):
            d = self.conf.defaults
            new_params, new_opt = {}, {}
            for name in self.topo:
                g = grads[name]
                if not g:
                    new_params[name] = params[name]
                    new_opt[name] = opt_state[name]
                    continue
                v = self.conf.vertices[name]
                layer = v.layer if isinstance(v, LayerVertex) else None
                gn = (layer.gradient_normalization if layer is not None and
                      layer.gradient_normalization is not None
                      else d.gradient_normalization)
                thr = (layer.gradient_normalization_threshold
                       if layer is not None and
                       layer.gradient_normalization_threshold is not None
                       else d.gradient_normalization_threshold)
                g = upd_mod.normalize_gradients(g, gn, thr)
                u = self._updaters[name]
                lr = (d.lr_schedule(u.learning_rate, iteration)
                      if d.lr_schedule else u.learning_rate)
                steps_tree, o_new = u.apply(g, opt_state[name], lr)
                p_new = jax.tree_util.tree_map(lambda p_, s_: p_ - s_,
                                               params[name], steps_tree)
                if layer is not None and layer.constraints:
                    p_new = apply_constraints(p_new, layer.constraints)
                new_params[name] = p_new
                new_opt[name] = o_new
            return new_params, new_opt

    def _train_step_fn(self):
        """The RAW (unjitted) single train step — `_build_train_step` wraps
        it in the one jit seam; the window engine (training/engine.py)
        scans it directly so donation stays at the outer seam."""
        def step(params, state, opt_state, iteration, rng, inputs, labels,
                 fmasks, lmasks):
            fsdp = getattr(self, "_fsdp_layout", None)
            with base_mod.iteration_scope(iteration):
                (score, (new_state, _)), grads = jax.value_and_grad(
                    self._loss, has_aux=True
                )(params, state, inputs, labels, rng, fmasks, lmasks)
            if fsdp is not None:
                # reduce-scatter seam (see MultiLayerNetwork._train_step_fn)
                grads = fsdp.shard_tree(grads)
            new_params, new_opt = self._apply_updates(params, grads,
                                                      opt_state, iteration)
            if fsdp is not None:
                # output sharding = input sharding: the donated window-scan
                # carry stays fsdp-sharded
                new_params = fsdp.shard_tree(new_params)
            return new_params, new_state, new_opt, score

        return step

    def _build_train_step(self):
        self._train_step_raw = self._train_step_fn()
        # jaxcompat.jit = jax.jit + the compile-watcher seam
        return jaxcompat.jit(self._train_step_raw, donate_argnums=(0, 1, 2),
                             watch_name="ComputationGraph.train_step")

    # ------------------------------------------------------------------
    # training / inference API
    # ------------------------------------------------------------------
    def fit(self, data, labels=None, epochs: int = 1, **attachments):
        """fit(MultiDataSet | DataSet | DataSetIterator | (features, labels)).

        The outer fit lifecycle — resume/save cadence, stall-watchdog
        heartbeats, listener firing order, crash-path flight bundles —
        is engine-owned (training/engine.py TrainingRun);
        `**attachments` forwards the resilience manager keyword there
        unchanged, with the same TOTAL-epoch-target resume contract as
        MultiLayerNetwork.fit (docs/RESILIENCE.md)."""
        from deeplearning4j_tpu.telemetry import introspect
        # the run restores any resume state FIRST, before steps build
        run = engine_mod.TrainingRun(self, "ComputationGraph.fit",
                                     epochs=epochs, **attachments)
        self._check_policy()
        if self._train_step is None:
            self._train_step = self._build_train_step()
        mds_iter = self._as_mds_iter(data, labels)
        loop = self._engine_loop(
            after_dispatch=lambda n, mds, elapsed:
                introspect.maybe_layer_spans(self, mds, self.iteration))
        return run.execute(loop, mds_iter)

    def _engine_loop(self, after_dispatch=None, window=None):
        """This graph's engine-loop wiring (stage / exec_one / raw step),
        shared by fit() and the distributed workers
        (engine.run_partition) so both ride ONE inner loop. Plain
        DataSet batches (the workers' shard shape) are adapted to
        MultiDataSet at the seam."""
        def to_mds(ds):
            return (ds if isinstance(ds, MultiDataSet)
                    else MultiDataSet.from_dataset(ds))

        def stage(ds):
            mds = to_mds(ds)
            if self._tbptt_mds(mds):
                return None  # tbptt chunk loop keeps its own dispatch
            inputs = tuple(jnp.asarray(f) for f in mds.features)
            labels = tuple(jnp.asarray(l) for l in mds.labels)
            fmasks = (tuple(None if m is None else jnp.asarray(m)
                            for m in mds.features_masks)
                      if mds.features_masks is not None else None)
            lmasks = (tuple(None if m is None else jnp.asarray(m)
                            for m in mds.labels_masks)
                      if mds.labels_masks is not None else None)
            return ((inputs, labels, fmasks, lmasks),
                    int(inputs[0].shape[0]))

        return engine_mod.WindowedFitLoop(
            self, raw_step=getattr(self, "_train_step_raw", None),
            stage=stage, dispatch=self._dispatch_step,
            # what `stage` declines: the tbptt chunk loop
            exec_one=lambda ds: self._fit_tbptt(to_mds(ds)),
            after_dispatch=after_dispatch, window=window,
            span_category="train", watch_prefix="ComputationGraph")

    def _dispatch_step(self, args):
        """One jitted train step on staged `(inputs, labels, fmasks, lmasks)`."""
        return engine_mod.dispatch_step(self, self._train_step, args)

    def _recurrent_vertices(self, for_streaming: bool = False):
        """for_streaming=True (rnnTimeStep) rejects bidirectional layers —
        stepwise streaming needs the sequence end (the reference throws,
        GravesBidirectionalLSTM.java:308-309). Under tBPTT they are allowed:
        forward state carries across chunks, the reverse scan is chunk-local
        (GravesBidirectionalLSTM.scan)."""
        from deeplearning4j_tpu.nn.layers.recurrent import (
            BaseRecurrent,
            LastTimeStep,
        )

        out = []
        for name in self.topo:
            v = self.conf.vertices[name]
            if not isinstance(v, LayerVertex):
                continue
            if isinstance(v.layer, BaseRecurrent):
                if for_streaming and not v.layer.streamable:
                    raise ValueError(
                        f"vertex {name!r} ({type(v.layer).__name__}) is "
                        f"bidirectional: rnnTimeStep needs a "
                        f"forward-only state carry")
                out.append(name)
            elif (isinstance(v.layer, LastTimeStep)
                  and isinstance(getattr(v.layer, "_inner", None),
                                 BaseRecurrent)):
                raise ValueError(
                    f"vertex {name!r} wraps a recurrent layer in "
                    f"LastTimeStep: its inner state cannot be carried "
                    f"across rnnTimeStep/tBPTT chunks — restructure as a "
                    f"recurrent layer + LastTimeStepVertex")
        return out

    def _init_carries(self, batch: int, for_streaming: bool = False):
        return {name: self.conf.vertices[name].layer.init_carry(batch)
                for name in self._recurrent_vertices(for_streaming)}

    def rnn_clear_previous_state(self):
        self._rnn_carries = None

    def rnn_time_step(self, *inputs):
        """Stateful streaming inference through the DAG
        (ComputationGraph.rnnTimeStep:2359): feed one or more timesteps,
        recurrent vertex state carries across calls."""
        arrs = [jnp.asarray(x) for x in inputs]
        single = arrs[0].ndim == 2
        if single:
            arrs = [a[:, None, :] if a.ndim == 2 else a for a in arrs]
        if getattr(self, "_rnn_carries", None) is None:
            self._rnn_carries = self._init_carries(arrs[0].shape[0],
                                                   for_streaming=True)
        acts, _, _, self._rnn_carries = self._forward(
            self.params, self.state, tuple(arrs), train=False, rng=None,
            stop_at_outputs=False, carries=self._rnn_carries)
        outs = [np.asarray(acts[o]) for o in self.conf.network_outputs]
        if single:
            outs = [o[:, 0] if o.ndim == 3 else o for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def _fit_tbptt(self, mds: MultiDataSet, put=None, report_batch=None):
        """Truncated BPTT through the DAG: time axis sliced into
        tbptt_fwd_length chunks, recurrent carries flow across chunks
        behind stop_gradient (calcBackpropGradients(truncatedBPTT):1626).
        `put`/`report_batch`: ParallelWrapper's placement hooks — see
        MultiLayerNetwork._fit_tbptt."""
        d = self.conf.defaults
        T = mds.features[0].shape[1]
        L = d.tbptt_fwd_length
        place = put if put is not None else (
            lambda a: None if a is None else jnp.asarray(a))
        if not getattr(self, "_checked_bidir_tbptt", False):
            from deeplearning4j_tpu.models.multi_layer_network import (
                warn_bidir_tbptt)

            warn_bidir_tbptt([n for n in self._recurrent_vertices(False)
                              if not self.conf.vertices[n].layer.streamable])
            self._checked_bidir_tbptt = True
        carries = self._init_carries(mds.features[0].shape[0])
        if put is not None:
            carries = jax.tree_util.tree_map(put, carries)
        step = self._get_tbptt_step()
        for t0 in range(0, T, L):
            sl = slice(t0, min(t0 + L, T))
            inputs = tuple(place(f[:, sl]) for f in mds.features)
            labels = tuple(place(l[:, sl]) for l in mds.labels)
            fmasks = (tuple(None if m is None else place(m[:, sl])
                            for m in mds.features_masks)
                      if mds.features_masks is not None else None)
            lmasks = (tuple(None if m is None else place(m[:, sl])
                            for m in mds.labels_masks)
                      if mds.labels_masks is not None else None)
            self._rng, sub = jax.random.split(self._rng)
            (self.params, self.state, self.opt_state, carries,
             score) = step(self.params, self.state, self.opt_state, carries,
                           jnp.asarray(self.iteration), sub, inputs, labels,
                           fmasks, lmasks)
            self.score_ = float(score)  # jaxlint: disable=JX010 — tbptt chunk boundary: carries thread host-side per chunk
            self.last_batch_size = (int(inputs[0].shape[0])
                                    if report_batch is None else report_batch)
            self.iteration += 1
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration, self.score_)

    def _get_tbptt_step(self):
        self._check_policy()
        if getattr(self, "_tbptt_step", None) is not None:
            return self._tbptt_step

        def loss_fn(params, state, carries, inputs, labels, rng, fmasks,
                    lmasks):
            # the ONE loss implementation, with carries threaded through
            return self._loss(params, state, inputs, labels, rng, fmasks,
                              lmasks, train=True, carries=carries)

        def step(params, state, opt_state, carries, iteration, rng, inputs,
                 labels, fmasks, lmasks):
            with base_mod.iteration_scope(iteration):
                (score, (new_state, new_carries)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, state, carries, inputs,
                                           labels, rng, fmasks, lmasks)
            new_params, new_opt = self._apply_updates(params, grads,
                                                      opt_state, iteration)
            # carries cross chunk boundaries without gradient flow
            new_carries = jax.tree_util.tree_map(jax.lax.stop_gradient,
                                                 new_carries)
            return new_params, new_state, new_opt, new_carries, score

        self._tbptt_step = jaxcompat.jit(
            step, donate_argnums=(0, 1, 2, 3),
            watch_name="ComputationGraph.tbptt_step")
        return self._tbptt_step

    def _tbptt_mds(self, mds) -> bool:
        """ONE predicate for the engine loop's stager and its fallback
        (`_engine_loop`'s stage / exec_one): a batch it holds for is not
        staged and runs the tbptt chunk loop.
        Per-sequence (2D) labels can't be time-sliced: standard BPTT
        instead, as the reference does for non-3D labels."""
        return (self.conf.defaults.backprop_type == "tbptt"
                and mds.features[0].ndim == 3
                and all(np.ndim(l) == 3 for l in mds.labels))

    def _as_mds_iter(self, data, labels):
        if isinstance(data, MultiDataSet):
            return lambda: iter([data])
        if isinstance(data, DataSet):
            return lambda: iter([MultiDataSet.from_dataset(data)])
        if isinstance(data, DataSetIterator):
            def gen():
                wrap = (not isinstance(data, AsyncDataSetIterator)
                        and data.async_supported())
                it_ = AsyncDataSetIterator(data) if wrap else data
                for ds in it_:
                    yield MultiDataSet.from_dataset(ds)
            return gen
        if isinstance(data, (list, tuple)) and labels is not None:
            return lambda: iter([MultiDataSet(
                [np.asarray(f) for f in data],
                [np.asarray(l) for l in (labels if isinstance(labels, (list, tuple)) else [labels])],
            )])
        if labels is not None:
            return lambda: iter([MultiDataSet([np.asarray(data)], [np.asarray(labels)])])
        raise TypeError(f"Cannot iterate {type(data)}")

    def output(self, *inputs, train: bool = False):
        """Forward to all output vertices; returns list (or single array)."""
        self._check_policy()
        if self._output_fn is None:
            def fwd(params, state, inputs_):
                acts, _, _, _ = self._forward(params, state, inputs_,
                                              train=False, rng=None,
                                              stop_at_outputs=False)
                return [acts[o] for o in self.conf.network_outputs]
            self._output_fn = jaxcompat.jit(
                fwd, watch_name="ComputationGraph.output")
        arrs = tuple(jnp.asarray(x) for x in inputs)
        outs = [np.asarray(o) for o in self._output_fn(self.params, self.state, arrs)]
        return outs[0] if len(outs) == 1 else outs

    def score(self, data: Union[DataSet, MultiDataSet]) -> float:
        mds = (MultiDataSet.from_dataset(data)
               if isinstance(data, DataSet) else data)
        inputs = tuple(jnp.asarray(f) for f in mds.features)
        labels = tuple(jnp.asarray(l) for l in mds.labels)
        fmasks = (tuple(None if m is None else jnp.asarray(m)
                        for m in mds.features_masks)
                  if mds.features_masks is not None else None)
        lmasks = (tuple(None if m is None else jnp.asarray(m)
                        for m in mds.labels_masks)
                  if mds.labels_masks is not None else None)
        s, _ = self._loss(self.params, self.state, inputs, labels,
                          jax.random.PRNGKey(0), fmasks, lmasks, train=False)
        return float(s)

    def _as_eval_mds(self, item):
        from deeplearning4j_tpu.datasets.dataset import DataSet

        return (MultiDataSet.from_dataset(item)
                if isinstance(item, DataSet) else item)

    def do_evaluation(self, iterator, *evaluations):
        """One pass over a DataSetIterator OR MultiDataSetIterator feeding
        every IEvaluation (ComputationGraph.java:3000 doEvaluation /
        :3063 MultiDataSetIterator overload). Multi-INPUT graphs are
        supported; like the reference this entry requires exactly one
        output array (ComputationGraph.java:3004-3007) — use
        evaluate_outputs() for multi-output graphs."""
        from deeplearning4j_tpu.eval import mask_aware_feeder

        if len(self.conf.network_outputs) != 1:
            raise ValueError(
                "do_evaluation requires a single-output graph "
                f"(have {len(self.conf.network_outputs)}); use "
                "evaluate_outputs() for per-output evaluation")
        feeders = [mask_aware_feeder(ev) for ev in evaluations]
        for item in iterator:
            mds = self._as_eval_mds(item)
            out = self.output(*mds.features)
            lmask = (mds.labels_masks[0]
                     if mds.labels_masks is not None else None)
            for feed in feeders:
                feed(mds.labels[0], out, lmask)
        return list(evaluations)

    def evaluate_outputs(self, iterator, evaluations):
        """Per-output evaluation of a multi-output graph in ONE pass.

        `evaluations` maps output vertex name (or output index) to an
        IEvaluation or list of IEvaluations; each is fed its output's
        predictions/labels (+ label mask) per batch and the same mapping is
        returned, merge-able across workers like every IEvaluation. The
        0.9.2 reference rejects >1 output arrays
        (ComputationGraph.java:3004-3007); later DL4J releases added this
        exact Map<Integer,IEvaluation[]> capability, and distributed eval
        (SURVEY.md §2.4) needs the merge-able per-output form."""
        from deeplearning4j_tpu.eval import mask_aware_feeder

        names = list(self.conf.network_outputs)
        by_idx: Dict[int, list] = {}
        for key, evs in evaluations.items():
            idx = key if isinstance(key, int) else names.index(key)
            if not 0 <= idx < len(names):
                raise ValueError(f"no output #{idx} (outputs: {names})")
            evs = evs if isinstance(evs, (list, tuple)) else [evs]
            by_idx[idx] = [mask_aware_feeder(ev) for ev in evs]
        for item in iterator:
            mds = self._as_eval_mds(item)
            outs = self.output(*mds.features)
            if len(names) == 1:
                outs = [outs]
            for idx, feeders in by_idx.items():
                lmask = (mds.labels_masks[idx]
                         if mds.labels_masks is not None else None)
                for feed in feeders:
                    feed(mds.labels[idx], outs[idx], lmask)
        return evaluations

    def _eval_with(self, iterator, ev):
        """Shared by the evaluate* family (ComputationGraph.evaluate/
        evaluateROC/evaluateRegression) — single-output graphs only, per
        reference semantics."""
        return self.do_evaluation(iterator, ev)[0]

    def evaluate(self, iterator):
        from deeplearning4j_tpu.eval.evaluation import Evaluation

        return self._eval_with(iterator, Evaluation())

    def evaluate_regression(self, iterator):
        from deeplearning4j_tpu.eval.regression import RegressionEvaluation

        return self._eval_with(iterator, RegressionEvaluation())

    def evaluate_roc(self, iterator, threshold_steps: int = 0):
        from deeplearning4j_tpu.eval.roc import ROC

        return self._eval_with(iterator, ROC(threshold_steps))

    def evaluate_roc_multi_class(self, iterator, threshold_steps: int = 0):
        from deeplearning4j_tpu.eval.roc import ROCMultiClass

        return self._eval_with(iterator, ROCMultiClass(threshold_steps))

    def evaluate_calibration(self, iterator, reliability_bins: int = 10,
                             histogram_bins: int = 50):
        from deeplearning4j_tpu.eval.calibration import EvaluationCalibration

        return self._eval_with(
            iterator, EvaluationCalibration(reliability_bins, histogram_bins))

    def get_param_table(self) -> Dict[str, np.ndarray]:
        flat = {}
        for name in self.topo:
            for pname, v in self.params[name].items():
                flat[f"{name}/{pname}"] = np.asarray(v)  # jaxlint: disable=JX010 — one-shot param export (serialization boundary)
        return flat
