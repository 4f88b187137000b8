"""MultiLayerNetwork — the sequential-network runtime.

Reference: nn/multilayer/MultiLayerNetwork.java:3157 — init():545,
fit(DataSetIterator):1165 (AsyncDataSetIterator wrap :1170),
computeGradientAndScore:2207-2247, calcBackpropGradients:1275, output:1886,
predict:1674, rnnTimeStep:2616, evaluate:2795, score(DataSet):2092, tBPTT
doTruncatedBPTT :1212-1214 with state carry :1474.

TPU-native redesign (SURVEY.md §3.1 'device boundary' note): the whole inner
training block — forward, loss, backward, gradient normalization, updater,
parameter step, constraints — is ONE jitted XLA program with donated
params/opt-state buffers (the functional replacement for DL4J's flat
param/gradient views + in-place step). Backprop is `jax.grad` over the pure
forward; there is no per-layer backpropGradient.

State model (all explicit, all pytrees):
    params     {"layer_i": {param pytree}}          — trained
    state      {"layer_i": {running stats etc.}}    — non-trained, updated fwd
    opt_state  [per-layer updater state]            — updater slots
    iteration  int                                   — schedule clock
Mutable-facade API (fit/output/...) wraps these functionally; `params` etc.
are donated into each step so HBM holds a single copy.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.util import jaxcompat
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn import losses as loss_mod
from deeplearning4j_tpu.nn import updaters as upd_mod
from deeplearning4j_tpu.nn import weightnoise as wn_mod
from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers import base as base_mod
from deeplearning4j_tpu.nn.layers.base import Layer
from deeplearning4j_tpu.nn.layers.output import BaseOutputLayer
from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrent
from deeplearning4j_tpu.nn.regularization import apply_constraints
from deeplearning4j_tpu.telemetry import introspect as introspect_mod
from deeplearning4j_tpu.telemetry.trace import device_scope
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.training import engine as engine_mod
from deeplearning4j_tpu.datasets.iterators import (
    AsyncDataSetIterator,
    DataSetIterator,
    ListDataSetIterator,
)

PyTree = Any


def _key(i: int) -> str:
    return f"layer_{i}"


def warn_bidir_tbptt(bidir: list) -> None:
    """One warning when bidirectional layers participate in tBPTT — a
    deliberate divergence from the reference, which refuses the
    configuration outright (GravesBidirectionalLSTM.java:89-93): here the
    backward half is chunk-local, so gradients see future context
    truncated to the tbptt window. Shared by MultiLayerNetwork and
    ComputationGraph; documented in docs/MIGRATION.md."""
    if not bidir:
        return
    import warnings

    warnings.warn(
        f"tBPTT with bidirectional layer(s) {bidir}: the backward scan "
        f"restarts at each chunk boundary, so future context is truncated "
        f"to the tbptt window (the reference rejects this configuration; "
        f"see docs/MIGRATION.md)", stacklevel=3)


class MultiLayerNetwork:
    """Mutable facade over a functional core. Construction does NOT allocate
    params; call init() (mirrors MultiLayerNetwork.init():545)."""

    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers: List[Layer] = conf.layers
        self.params: Optional[Dict[str, PyTree]] = None
        self.state: Optional[Dict[str, PyTree]] = None
        self.opt_state: Optional[List[PyTree]] = None
        self.iteration: int = 0
        self.epoch: int = 0
        self.listeners: List = []
        self.score_: float = float("nan")
        self.last_batch_size: int = 0
        self.last_etl_time_ms: float = 0.0
        self._rng = jax.random.PRNGKey(conf.defaults.seed)
        self._train_step = None
        self._output_fn = None
        self._tbptt_step = None
        self._policy_fp = dtypes.policy_fingerprint()
        self._rnn_carries: Optional[list] = None  # rnnTimeStep state
        self._tbptt_carries: Optional[list] = None

        self._input_types = conf.layer_input_types()
        self._updaters = self._resolve_updaters()

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------
    def _check_policy(self):
        """Invalidate cached jitted fns when the global precision policy
        changed since they were traced (dtypes.policy_fingerprint)."""
        fp = dtypes.policy_fingerprint()
        if getattr(self, "_policy_fp", None) != fp:
            self._policy_fp = fp
            self._train_step = None
            self._output_fn = None
            self._tbptt_step = None

    def _resolve_updaters(self) -> List[upd_mod.Updater]:
        out = []
        for i, l in enumerate(self.layers):
            u = l.updater if l.updater is not None else self.conf.defaults.updater
            u = upd_mod.get(u)
            if l.learning_rate is not None:
                import copy

                u = copy.copy(u)
                u.learning_rate = l.learning_rate
            out.append(u)
        return out

    @introspect_mod.init_span()
    def init(self, params: Optional[Dict[str, PyTree]] = None) -> "MultiLayerNetwork":
        key = jax.random.PRNGKey(self.conf.defaults.seed)
        keys = jax.random.split(key, len(self.layers))
        self.params = params or {}
        self.state = {}
        for i, layer in enumerate(self.layers):
            in_type = self._input_types[i]
            if params is None:
                self.params[_key(i)] = (
                    layer.init_params(keys[i], in_type) if layer.has_params() else {}
                )
            self.state[_key(i)] = layer.init_state(in_type)
        self.opt_state = [
            self._updaters[i].init_state(self.params[_key(i)])
            for i in range(len(self.layers))
        ]
        return self

    def num_params(self) -> int:
        leaves = jax.tree_util.tree_leaves(self.params)
        return int(sum(l.size for l in leaves))

    def summary(self) -> str:
        lines = ["=" * 70]
        lines.append(f"{'idx':<4}{'layer':<28}{'in -> out':<26}{'params':>10}")
        lines.append("-" * 70)
        for i, l in enumerate(self.layers):
            n = sum(x.size for x in jax.tree_util.tree_leaves(self.params[_key(i)])) if self.params else 0
            lines.append(
                f"{i:<4}{type(l).__name__:<28}"
                f"{str(self._input_types[i].shape())+'->'+str(self._input_types[i+1].shape()):<26}"
                f"{n:>10}"
            )
        lines.append("-" * 70)
        lines.append(f"total params: {self.num_params() if self.params else 0}")
        lines.append("=" * 70)
        return "\n".join(lines)

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listeners(self, *listeners):
        self.listeners.extend(listeners)
        return self

    # ------------------------------------------------------------------
    # pure functional core
    # ------------------------------------------------------------------
    def _forward(self, params, state, x, *, train: bool, rng, mask=None,
                 to_layer: Optional[int] = None, carries: Optional[list] = None):
        """Forward through layers [0, to_layer). Returns (activation, new_state,
        new_carries). `carries` enables stateful RNN eval (rnnTimeStep/tBPTT)."""
        n = len(self.layers) if to_layer is None else to_layer
        new_state = dict(state)
        new_carries = list(carries) if carries is not None else None
        cur_mask = mask
        rngs = (jax.random.split(rng, n) if rng is not None else [None] * n)
        # fsdp gather-on-use hook (parallel/layout.py, attached by
        # ParallelWrapper when the mesh's fsdp axis is >1): params arrive
        # SHARDED; each layer's subtree is gathered right before use, and
        # the gather runs INSIDE the layer's remat scope so the backward
        # pass re-gathers instead of stashing full-width residuals
        fsdp = getattr(self, "_fsdp_layout", None)
        for i in range(n):
            layer = self.layers[i]
            if i in self.conf.input_preprocessors:
                x = self.conf.input_preprocessors[i].transform(x, cur_mask)
            k = _key(i)
            # the layer's device scope (telemetry/trace.py) is opened
            # INSIDE what remat wraps: the recompute carries it too
            if carries is not None and isinstance(layer, BaseRecurrent):
                with device_scope(kind=type(layer).__name__, layer=i):
                    p_i = (params[k] if fsdp is None
                           else fsdp.gather(k, params[k]))
                    p_i = wn_mod.maybe_transform(layer, p_i, rngs[i], train)
                    x, c_out = layer.scan(p_i, x, carries[i], mask=cur_mask,
                                          train=train, rng=rngs[i])
                new_carries[i] = c_out
            else:
                def run(p_raw, xx, st, r, m, _layer=layer, _k=k, _i=i):
                    with device_scope(kind=type(_layer).__name__, layer=_i):
                        p_g = (p_raw if fsdp is None
                               else fsdp.gather(_k, p_raw))
                        p_g = wn_mod.maybe_transform(_layer, p_g, r, train)
                        return _layer.apply(p_g, xx, state=st, train=train,
                                            rng=r, mask=m)

                pol = getattr(layer, "remat", None)
                if train and pol:
                    # local import: parallel/__init__ pulls in wrapper,
                    # which reaches back into models at import time
                    from deeplearning4j_tpu.parallel import (
                        layout as layout_mod,
                    )

                    run = layout_mod.maybe_remat(run, pol)
                x, s = run(params[k], x, state[k], rngs[i], cur_mask)
                if train:
                    new_state[k] = s
            cur_mask = layer.propagate_mask(cur_mask, self._input_types[i])
        return x, new_state, new_carries, cur_mask

    def _reg_score(self, params):
        """L1/L2 penalty over all layers (BaseLayer.calcL1/calcL2)."""
        total = jnp.zeros(())
        d = self.conf.defaults
        for i, layer in enumerate(self.layers):
            p = params[_key(i)]
            if not p:
                continue
            l1 = layer.l1 if layer.l1 is not None else d.l1
            l2 = layer.l2 if layer.l2 is not None else d.l2
            l1b = layer.l1_bias if layer.l1_bias is not None else d.l1_bias
            l2b = layer.l2_bias if layer.l2_bias is not None else d.l2_bias
            if l1 or l2:
                reg = layer.regularizable(p)
                for v in jax.tree_util.tree_leaves(reg):
                    if l1:
                        total = total + l1 * jnp.sum(jnp.abs(v))
                    if l2:
                        total = total + 0.5 * l2 * jnp.sum(v * v)
            if l1b or l2b:
                for name, v in p.items():
                    if name.startswith("b"):
                        if l1b:
                            total = total + l1b * jnp.sum(jnp.abs(v))
                        if l2b:
                            total = total + 0.5 * l2b * jnp.sum(v * v)
        return total

    def _loss(self, params, state, x, y, rng, fmask, lmask, train=True):
        out_layer = self.layers[-1]
        assert isinstance(out_layer, BaseOutputLayer), (
            "last layer must be an output layer (Output/RnnOutput/LossLayer/...)"
        )
        h, new_state, _, cur_mask = self._forward(
            params, state, x, train=train, rng=rng, mask=fmask,
            to_layer=len(self.layers) - 1
        )
        k = _key(len(self.layers) - 1)
        eff_mask = lmask if lmask is not None else cur_mask
        fsdp = getattr(self, "_fsdp_layout", None)
        with device_scope(kind="loss"):  # head, loss and the penalty
            p_out = params[k] if fsdp is None else fsdp.gather(k, params[k])
            p_out = wn_mod.maybe_transform(out_layer, p_out, rng, train)
            score, per_ex, out_state = out_layer.compute_loss(
                p_out, h, y, state=state[k], mask=eff_mask, rng=rng
            )
            score = score + self._reg_score(params)
        new_state[k] = out_state
        return score, new_state

    def _apply_updates(self, params, grads, opt_state, iteration):
        """Per-layer gradient-normalization + updater + constraints —
        shared by the standard train step, the tBPTT step, and
        ParallelWrapper's sequence-parallel step (which computes grads
        under shard_map and applies them here)."""
        with device_scope(kind="update"):
            d = self.conf.defaults
            schedule = d.lr_schedule
            new_params, new_opt = {}, []
            for i in range(len(self.layers)):
                k = _key(i)
                g = grads[k]
                layer = self.layers[i]
                if not g or getattr(layer, "frozen", False):
                    new_params[k] = params[k]
                    new_opt.append(opt_state[i])
                    continue
                gn = (layer.gradient_normalization
                      if layer.gradient_normalization is not None
                      else d.gradient_normalization)
                thr = (layer.gradient_normalization_threshold
                       if layer.gradient_normalization_threshold is not None
                       else d.gradient_normalization_threshold)
                g = upd_mod.normalize_gradients(g, gn, thr)
                u = self._updaters[i]
                base_lr = u.learning_rate
                lr = schedule(base_lr, iteration) if schedule else base_lr
                steps_tree, new_ou = u.apply(g, opt_state[i], lr)
                p = jax.tree_util.tree_map(
                    lambda p_, s_: p_ - s_, params[k], steps_tree
                )
                if layer.constraints:
                    p = apply_constraints(p, layer.constraints)
                new_params[k] = p
                new_opt.append(new_ou)
            return new_params, new_opt

    def _train_step_fn(self):
        """The RAW (unjitted) single train step — `_build_train_step` wraps
        it in the one jit seam; the window engine (training/engine.py)
        scans it directly so donation stays at the outer seam."""
        def step(params, state, opt_state, iteration, rng, x, y, fmask, lmask):
            fsdp = getattr(self, "_fsdp_layout", None)
            with base_mod.iteration_scope(iteration):
                (score, new_state), grads = jax.value_and_grad(
                    self._loss, has_aux=True
                )(params, state, x, y, rng, fmask, lmask)
            if fsdp is not None:
                # reduce-scatter seam: cotangents from the per-layer
                # gathers land here full-width; constraining them to the
                # sharded-at-rest specs lets XLA fuse the data-axis psum
                # into a reduce-scatter, so updater math runs 1/fsdp-sized
                grads = fsdp.shard_tree(grads)
            new_params, new_opt = self._apply_updates(params, grads,
                                                      opt_state, iteration)
            if fsdp is not None:
                # pin the output sharding = input sharding so the window
                # engine's donated scan carry stays fsdp-sharded
                new_params = fsdp.shard_tree(new_params)
            return new_params, new_state, new_opt, score

        return step

    def _build_train_step(self):
        d = self.conf.defaults
        if d.optimization_algo not in ("stochastic_gradient_descent", "sgd"):
            import warnings

            warnings.warn(
                f"optimization_algo={d.optimization_algo!r} is only honored "
                "by MultiLayerNetwork.fit on 2D batches; this path (tBPTT / "
                "ParallelWrapper / prebuilt train step) uses the SGD updater "
                "step instead.", stacklevel=2)

        self._train_step_raw = self._train_step_fn()
        # jaxcompat.jit = jax.jit + the compile-watcher seam: the train
        # step is THE retrace hotspot (shape churn lands here first)
        return jaxcompat.jit(self._train_step_raw, donate_argnums=(0, 1, 2),
                             watch_name="MultiLayerNetwork.train_step")

    # ------------------------------------------------------------------
    # training API
    # ------------------------------------------------------------------
    def fit(self, data, labels=None, epochs: int = 1, **attachments):
        """fit(DataSetIterator) | fit(DataSet) | fit(features, labels).

        Mirrors MultiLayerNetwork.fit(DataSetIterator):1165 — wraps the
        iterator for async prefetch and runs the train step through the
        engine loop. The whole outer lifecycle — resume/save cadence,
        stall-watchdog heartbeats, listener firing order, crash-path
        flight bundles, telemetry spans — is engine-owned
        (training/engine.py TrainingRun); `**attachments` forwards the
        resilience manager keyword there unchanged, with the same
        TOTAL-epoch-target resume contract as before
        (docs/RESILIENCE.md)."""
        from deeplearning4j_tpu.telemetry import introspect
        # the run restores any resume state FIRST, before steps build
        run = engine_mod.TrainingRun(self, "MultiLayerNetwork.fit",
                                     epochs=epochs, **attachments)
        iterator = self._as_iterator(data, labels)
        use_tbptt = self.conf.defaults.backprop_type == "tbptt"
        uses_sgd_step = (use_tbptt or self.conf.defaults.optimization_algo
                         in ("stochastic_gradient_descent", "sgd"))
        self._check_policy()
        if self._train_step is None and uses_sgd_step:
            self._train_step = self._build_train_step()
        loop = self._engine_loop(
            after_dispatch=lambda n, ds, elapsed:
                introspect.maybe_layer_spans(self, ds, self.iteration))
        return run.execute(loop, iterator)

    def _engine_loop(self, after_dispatch=None, window=None):
        """This model's engine-loop wiring (stage / exec_one / raw step),
        shared by fit() and the distributed workers
        (engine.run_partition) so both ride ONE inner loop."""
        use_tbptt = self.conf.defaults.backprop_type == "tbptt"
        sgd = self.conf.defaults.optimization_algo in (
            "stochastic_gradient_descent", "sgd")

        def tbptt_batch(ds):
            # ONE predicate for both the fallback router and the stager —
            # the engine's K-window == K-steps guarantee needs exec_one
            # and stage to agree on which batches are staged.
            # Per-sequence (2D) labels can't be time-sliced: standard
            # BPTT instead, as the reference does for non-3D labels
            # (and ComputationGraph._tbptt_mds here)
            return (use_tbptt and ds.features.ndim == 3
                    and ds.labels.ndim == 3)

        def exec_one(ds):
            # what `stage` declines: the tbptt chunk loop, else the
            # line-search solver
            if tbptt_batch(ds):
                self._fit_tbptt(ds)
            else:
                self._fit_batch_solver(ds)

        def stage(ds):
            # tbptt chunk loops and the line-search solver keep their own
            # dispatch; only the standard jitted SGD step is staged
            if not sgd or tbptt_batch(ds):
                return None
            x = jnp.asarray(ds.features)
            y = jnp.asarray(ds.labels)
            fm = (None if ds.features_mask is None
                  else jnp.asarray(ds.features_mask))
            lm = (None if ds.labels_mask is None
                  else jnp.asarray(ds.labels_mask))
            return (x, y, fm, lm), int(x.shape[0])

        return engine_mod.WindowedFitLoop(
            self, raw_step=getattr(self, "_train_step_raw", None),
            stage=stage, dispatch=self._dispatch_step, exec_one=exec_one,
            after_dispatch=after_dispatch, window=window,
            span_category="train", watch_prefix="MultiLayerNetwork")

    def _dispatch_step(self, args):
        """One jitted train step on staged `(x, y, fm, lm)`."""
        return engine_mod.dispatch_step(self, self._train_step, args)

    def _fit_batch_solver(self, ds: DataSet):
        """Line-search solver path (Solver.java → ConjugateGradient/LBFGS/
        LineGradientDescent per conf.optimization_algo). One solver iteration
        per batch; CG/LBFGS curvature state persists across batches. Frozen
        layers are excluded from the optimized vector; per-layer gradient
        normalization is applied inside value_and_grad; constraints and layer
        state (BN running stats) are refreshed after the step, matching the
        SGD train-step semantics."""
        from deeplearning4j_tpu.optimize import solvers as solver_mod

        x = jnp.asarray(ds.features)
        y = jnp.asarray(ds.labels)
        fm = None if ds.features_mask is None else jnp.asarray(ds.features_mask)
        lm = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
        self._rng, sub = jax.random.split(self._rng)

        if getattr(self, "_solver", None) is None:
            d = self.conf.defaults
            layers = self.layers
            frozen_keys = frozenset(
                _key(i) for i, l in enumerate(layers)
                if getattr(l, "frozen", False))
            self._solver_frozen_keys = frozen_keys

            def value_and_grad(train_params, frozen_params, state, x, y, rng,
                               fm, lm):
                def loss_of(tp):
                    full = {**frozen_params, **tp}
                    s, _ = self._loss(full, state, x, y, rng, fm, lm,
                                      train=True)
                    return s

                score, grads = jax.value_and_grad(loss_of)(train_params)
                normed = {}
                for i, layer in enumerate(layers):
                    k = _key(i)
                    if k not in grads:
                        continue
                    gn = (layer.gradient_normalization
                          if layer.gradient_normalization is not None
                          else d.gradient_normalization)
                    thr = (layer.gradient_normalization_threshold
                           if layer.gradient_normalization_threshold is not None
                           else d.gradient_normalization_threshold)
                    normed[k] = upd_mod.normalize_gradients(grads[k], gn, thr)
                return score, normed

            lr = (d.updater.learning_rate if d.learning_rate is None
                  else d.learning_rate)
            self._solver = solver_mod.Solver(
                d.optimization_algo, value_and_grad, learning_rate=lr,
                max_line_search_iterations=d.max_num_line_search_iterations)
            # only stateful layers (BN running stats etc.) need the refresh
            self._solver_state_refresh = (
                jax.jit(lambda p, st, x, y, rng, fm, lm:
                        self._loss(p, st, x, y, rng, fm, lm, train=True)[1])
                if jax.tree_util.tree_leaves(self.state) else None)

        frozen_keys = self._solver_frozen_keys
        train_params = {k: v for k, v in self.params.items()
                        if k not in frozen_keys}
        frozen_params = {k: v for k, v in self.params.items()
                         if k in frozen_keys}
        train_params, score = self._solver.optimize(
            train_params, frozen_params, self.state, x, y, sub, fm, lm)
        new_params = {**frozen_params, **train_params}
        for i, layer in enumerate(self.layers):
            k = _key(i)
            if layer.constraints and k not in frozen_keys:
                new_params[k] = apply_constraints(new_params[k],
                                                  layer.constraints)
        self.params = new_params
        if self._solver_state_refresh is not None:
            self.state = self._solver_state_refresh(
                self.params, self.state, x, y, sub, fm, lm)
        self.score_ = float(score)
        self.last_batch_size = int(x.shape[0])
        self.iteration += 1
        for lst in self.listeners:
            lst.iteration_done(self, self.iteration, self.score_)

    def _fit_tbptt(self, ds: DataSet, put=None, report_batch=None):
        """Truncated BPTT (MultiLayerNetwork.doTruncatedBPTT): slice the time
        axis into fwd-length chunks; RNN carries flow across chunks via
        stop_gradient (state carry :1474).

        `put` (optional) places each chunk array and carry leaf on
        device — ParallelWrapper passes a batch-axis-sharding device_put
        so THIS loop (not a copy of it) runs the dp/tp tbptt path;
        `report_batch` overrides last_batch_size (the wrapper reports the
        unpadded size)."""
        T = ds.features.shape[1]
        L = self.conf.defaults.tbptt_fwd_length
        place = put if put is not None else (
            lambda a: None if a is None else jnp.asarray(a))
        if not getattr(self, "_checked_bidir_tbptt", False):
            warn_bidir_tbptt([type(l).__name__ for l in self.layers
                              if isinstance(l, BaseRecurrent)
                              and not l.streamable])
            self._checked_bidir_tbptt = True
        carries = self._init_carries(ds.features.shape[0])
        if put is not None:
            carries = jax.tree_util.tree_map(put, carries)
        step = self._get_tbptt_step()
        for t0 in range(0, T, L):
            sl = slice(t0, min(t0 + L, T))
            x = place(ds.features[:, sl])
            y = place(ds.labels[:, sl])
            fm = (None if ds.features_mask is None
                  else place(ds.features_mask[:, sl]))
            lm = (None if ds.labels_mask is None
                  else place(ds.labels_mask[:, sl]))
            self._rng, sub = jax.random.split(self._rng)
            self.params, self.state, self.opt_state, carries, score = step(
                self.params, self.state, self.opt_state, carries,
                jnp.asarray(self.iteration), sub, x, y, fm, lm,
            )
            self.score_ = float(score)  # jaxlint: disable=JX010 — tbptt chunk boundary: carries thread host-side per chunk
            self.last_batch_size = (int(x.shape[0]) if report_batch is None
                                    else report_batch)
            self.iteration += 1
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration, self.score_)

    def _get_tbptt_step(self):
        self._check_policy()
        if getattr(self, "_tbptt_step", None) is not None:
            return self._tbptt_step
        d = self.conf.defaults
        updaters = self._updaters
        n_layers = len(self.layers)

        def loss_fn(params, state, carries, x, y, rng, fmask, lmask):
            out_layer = self.layers[-1]
            h, new_state, new_carries, cur_mask = self._forward(
                params, state, x, train=True, rng=rng, mask=fmask,
                to_layer=n_layers - 1, carries=carries,
            )
            k = _key(n_layers - 1)
            eff_mask = lmask if lmask is not None else cur_mask
            with device_scope(kind="loss"):
                score, per_ex, out_state = out_layer.compute_loss(
                    params[k], h, y, state=state[k], mask=eff_mask, rng=rng
                )
                score = score + self._reg_score(params)
            new_state[k] = out_state
            return score, (new_state, new_carries)

        def step(params, state, opt_state, carries, iteration, rng, x, y,
                 fmask, lmask):
            with base_mod.iteration_scope(iteration):
                (score, (new_state, new_carries)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params, state, carries, x, y, rng, fmask, lmask)
            new_carries = jax.tree_util.tree_map(
                jax.lax.stop_gradient, new_carries
            )
            new_params, new_opt = self._apply_updates(params, grads,
                                                      opt_state, iteration)
            return new_params, new_state, new_opt, new_carries, score

        self._tbptt_step = jaxcompat.jit(
            step, donate_argnums=(0, 1, 2, 3),
            watch_name="MultiLayerNetwork.tbptt_step")
        return self._tbptt_step

    def _init_carries(self, batch, for_streaming: bool = False):
        """Carry pytrees for the recurrent layers.

        for_streaming=True (rnnTimeStep) rejects bidirectional layers — a
        backward scan needs the sequence end, so stepwise streaming is
        ill-defined (the reference throws the same way,
        GravesBidirectionalLSTM.java:308-309). Under tBPTT (for_streaming=
        False) bidirectional layers ARE allowed: the forward half carries
        state across chunks like any LSTM, the backward half is chunk-local
        (GravesBidirectionalLSTM.scan starts its reverse scan fresh at each
        chunk's end)."""
        if for_streaming:
            for l in self.layers:
                if isinstance(l, BaseRecurrent) and not l.streamable:
                    raise ValueError(
                        f"{type(l).__name__} is bidirectional: rnnTimeStep "
                        f"needs a forward-only state carry (backward scan "
                        f"requires the sequence end)")
        return [
            l.init_carry(batch) if isinstance(l, BaseRecurrent) else None
            for l in self.layers
        ]

    def _as_iterator(self, data, labels) -> DataSetIterator:
        if isinstance(data, DataSetIterator):
            if data.async_supported() and not isinstance(data, AsyncDataSetIterator):
                return AsyncDataSetIterator(data)
            return data
        if isinstance(data, DataSet):
            return ListDataSetIterator(data, batch=data.num_examples())
        if labels is not None:
            ds = DataSet(np.asarray(data), np.asarray(labels))
            return ListDataSetIterator(ds, batch=ds.num_examples())
        raise TypeError(f"Cannot build iterator from {type(data)}")

    # ------------------------------------------------------------------
    # layerwise pretraining (MultiLayerNetwork.pretrain / pretrainLayer)
    # ------------------------------------------------------------------
    def pretrain(self, iterator, epochs: int = 1):
        """Greedy layerwise unsupervised pretraining: every layer exposing
        `pretrain_loss` (AutoEncoder/VAE/RBM) is trained in turn on the
        activations of the layers below it."""
        for i, layer in enumerate(self.layers):
            if hasattr(layer, "pretrain_loss"):
                self.pretrain_layer(i, iterator, epochs=epochs)
        return self

    def pretrain_layer(self, layer_idx: int, iterator, epochs: int = 1):
        layer = self.layers[layer_idx]
        if not hasattr(layer, "pretrain_loss"):
            raise ValueError(f"layer {layer_idx} has no pretrain objective")
        u = self._updaters[layer_idx]
        opt = u.init_state(self.params[_key(layer_idx)])

        def loss_fn(p, x, rng):
            return layer.pretrain_loss(p, x, rng)

        @jax.jit
        def step(p, opt_state, x, rng):
            l, g = jax.value_and_grad(loss_fn)(p, x, rng)
            steps_tree, new_opt = u.apply(g, opt_state, u.learning_rate)
            return (jax.tree_util.tree_map(lambda a, s: a - s, p, steps_tree),
                    new_opt, l)

        @jax.jit
        def below(params, state, x):
            h, _, _, _ = self._forward(params, state, x, train=False,
                                       rng=None, to_layer=layer_idx)
            return h

        it_ = self._as_iterator(iterator, None)
        p = self.params[_key(layer_idx)]
        for _ in range(epochs):
            for ds in it_:
                self._rng, sub = jax.random.split(self._rng)
                h = below(self.params, self.state, jnp.asarray(ds.features))
                p, opt, l = step(p, opt, h, sub)
                self.score_ = float(l)  # jaxlint: disable=JX010 — layerwise pretraining (cold path, per-batch loss readout)
        self.params[_key(layer_idx)] = p
        return self

    # ------------------------------------------------------------------
    # inference API
    # ------------------------------------------------------------------
    def output(self, x, train: bool = False) -> np.ndarray:
        """Full forward pass (MultiLayerNetwork.output:1886)."""
        self._check_policy()
        if self._output_fn is None:
            def fwd(params, state, x_):
                h, _, _, _ = self._forward(params, state, x_, train=False,
                                           rng=None)
                return h
            self._output_fn = jaxcompat.jit(
                fwd, watch_name="MultiLayerNetwork.output")
        return np.asarray(self._output_fn(self.params, self.state, jnp.asarray(x)))

    def feed_forward(self, x, train: bool = False) -> List[np.ndarray]:
        """All layer activations incl. input (feedForward)."""
        acts = [np.asarray(x)]
        h = jnp.asarray(x)
        cur_mask = None
        for i, layer in enumerate(self.layers):
            if i in self.conf.input_preprocessors:
                h = self.conf.input_preprocessors[i].transform(h, cur_mask)
            h, _ = layer.apply(self.params[_key(i)], h,
                               state=self.state[_key(i)], train=False,
                               rng=None, mask=cur_mask)
            acts.append(np.asarray(h))  # jaxlint: disable=JX010 — feed_forward returns eager per-layer host activations by contract
        return acts

    def predict(self, x) -> np.ndarray:
        """Argmax class ids (predict:1674)."""
        return np.argmax(self.output(x), axis=-1)

    def score(self, ds: DataSet, training: bool = False) -> float:
        """Loss on a dataset (score(DataSet):2092)."""
        x = jnp.asarray(ds.features)
        y = jnp.asarray(ds.labels)
        fm = None if ds.features_mask is None else jnp.asarray(ds.features_mask)
        lm = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
        rng = jax.random.PRNGKey(0)
        s, _ = self._loss(self.params, self.state, x, y, rng, fm, lm,
                          train=training)
        return float(s)

    def evaluate(self, iterator, metric: str = "classification"):
        """Classification eval over an iterator (evaluate:2795)."""
        from deeplearning4j_tpu.eval import Evaluation, eval_over

        return eval_over(self.output, iterator, Evaluation())

    def evaluate_regression(self, iterator):
        from deeplearning4j_tpu.eval import RegressionEvaluation, eval_over

        return eval_over(self.output, iterator, RegressionEvaluation())

    def evaluate_roc(self, iterator, threshold_steps: int = 0):
        from deeplearning4j_tpu.eval import ROC, eval_over

        return eval_over(self.output, iterator, ROC(threshold_steps))

    def evaluate_roc_multi_class(self, iterator, threshold_steps: int = 0):
        """One-vs-all ROC per class (evaluateROCMultiClass)."""
        from deeplearning4j_tpu.eval import ROCMultiClass, eval_over

        return eval_over(self.output, iterator,
                         ROCMultiClass(threshold_steps))

    def evaluate_calibration(self, iterator, reliability_bins: int = 10,
                             histogram_bins: int = 50):
        """Reliability diagrams + probability histograms
        (doEvaluation with EvaluationCalibration)."""
        from deeplearning4j_tpu.eval import EvaluationCalibration, eval_over

        return eval_over(self.output, iterator,
                         EvaluationCalibration(reliability_bins,
                                               histogram_bins))

    # ------------------------------------------------------------------
    # stateful RNN inference (rnnTimeStep:2616)
    # ------------------------------------------------------------------
    def rnn_clear_previous_state(self):
        self._rnn_carries = None

    def rnn_time_step(self, x) -> np.ndarray:
        """Feed one or more timesteps, carrying hidden state across calls.
        x: [b, t, f] (or [b, f] for a single step)."""
        x = jnp.asarray(x)
        single = x.ndim == 2
        if single:
            x = x[:, None, :]
        if self._rnn_carries is None:
            self._rnn_carries = self._init_carries(x.shape[0],
                                                   for_streaming=True)
        h, _, self._rnn_carries, _ = self._forward(
            self.params, self.state, x, train=False, rng=None,
            carries=self._rnn_carries,
        )
        out = np.asarray(h)
        return out[:, 0] if (single and out.ndim == 3) else out

    # ------------------------------------------------------------------
    # persistence
    # ------------------------------------------------------------------
    def get_param_table(self) -> Dict[str, np.ndarray]:
        """Flat {"layer_i/name": array} view (paramTable())."""
        flat = {}
        for i in range(len(self.layers)):
            for name, v in self.params[_key(i)].items():
                flat[f"{_key(i)}/{name}"] = np.asarray(v)  # jaxlint: disable=JX010 — one-shot param export (serialization boundary)
        return flat

    def set_param_table(self, table: Dict[str, np.ndarray]):
        for full, v in table.items():
            k, name = full.split("/", 1)
            self.params[k][name] = jnp.asarray(v)

    def clone(self) -> "MultiLayerNetwork":
        other = MultiLayerNetwork(
            MultiLayerConfiguration.from_json(self.conf.to_json())
        )
        other.init()
        # deep-copy buffers: fit() donates params/state into the train step,
        # so sharing buffers with the clone would delete them under us
        other.params = jax.tree_util.tree_map(lambda a: a.copy(), self.params)
        other.state = jax.tree_util.tree_map(lambda a: a.copy(), self.state)
        return other
