"""Windowed, device-resident training step engine.

The per-step host round-trip is the fit loops' hidden tax: every
minibatch pays one jit dispatch, one `float(score)` device sync, and one
round of listener/heartbeat bookkeeping. Each is a span of the step
(`put`, `dispatch`, `score_wait`, `listeners`; docs/TELEMETRY.md), so
`telemetry.fit_log()` says after any fit what share of a step it was;
PERF.md section 5 has it for the benchmark's cell. K defaults to 1.

This module rolls K optimizer steps into ONE jitted `lax.scan` with a
donated `(params, state, opt_state, rng)` carry and a pre-staged
on-device batch window, so host dispatch, listener bookkeeping, and
metric reads happen once per window instead of once per step:

    window scan:  (params, state, opt, rng, it0), [K batches]
                      -> (params', state', opt', rng', [K scores])

Semantics are preserved, observed at window boundaries: the scan returns
the per-step score vector, and the engine replays it through
`iteration_done` one step at a time (score_, iteration, last_batch_size
advance per step exactly as the per-step loop would), so the
DivergenceSentry still trips on a NaN injected mid-window, heartbeats
still see every iteration, and checkpoint cadence (epoch end) is
untouched. Recovery granularity DOES coarsen to the window: listeners
that snapshot state (the sentry) are offered `on_window_start` before
each dispatch so their restore point is the clean pre-window state, not
a mid-burst one (docs/PERFORMANCE.md "windowed mode").

`DL4J_TPU_STEP_WINDOW` defaults to 1: one dispatch a step, with ONE
BATCH OF LOOK-AHEAD on the fit thread. After step k's jitted call has
returned and before the thread blocks for its score, the loop takes
batch k+1 from the iterator and hands it to the runtime, so its
host->device transfer runs while the chip computes step k; listeners,
rng schedule, scores and parameters are bitwise those of a loop that
feeds one batch at a time (`WindowedFitLoop._run_ahead`,
docs/PERFORMANCE.md "One batch of look-ahead"). All three fit paths
delegate their inner loop here; the per-path deltas (tbptt chunking,
ParallelWrapper's mesh placement and chaos site) ride the callbacks.

This module is also THE owner of the outer fit lifecycle. `TrainingRun`
holds every attachment the fit paths used to wire by hand, in
triplicate: checkpoint resume/save cadence, the stall-watchdog
heartbeat, the HBM watermark tracker, the fit-level TraceContext, the
TrainingListener firing order (on_fit_start / per-epoch / on_fit_end),
and the crash-path flight bundle. MultiLayerNetwork.fit,
ComputationGraph.fit and ParallelWrapper.fit are thin facades that
build their staging callbacks and hand the rest to `TrainingRun`; the
distributed masters ride the same loop through `run_partition` (worker
shards) and `master_session` (the master-level heartbeat/trace
lifecycle). One place to wire every future knob.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from deeplearning4j_tpu.telemetry import counters as counters_mod
from deeplearning4j_tpu.telemetry import trace as trace_mod
from deeplearning4j_tpu.util import compile_cache
from deeplearning4j_tpu.util import envflags
from deeplearning4j_tpu.util import jaxcompat

PyTree = Any
_END = object()

_WINDOW_GATE = "DL4J_TPU_STEP_WINDOW"

_STEP_SECONDS = None


def _step_hist():
    """``dl4j_tpu_step_seconds`` — per-step wall time, the SLO engine's
    step-time objective input (telemetry/slo.py). Created lazily and
    observed only while telemetry is on, so the gate-off hot loop keeps
    its zero-telemetry-cost contract."""
    global _STEP_SECONDS
    if _STEP_SECONDS is None:
        from deeplearning4j_tpu.telemetry import metrics as metrics_mod

        _STEP_SECONDS = metrics_mod.histogram(
            "dl4j_tpu_step_seconds",
            "Optimizer step wall time (windowed dispatches record "
            "elapsed/n per step)",
            buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                     0.25, 0.5, 1.0, 2.5, 5.0, 10.0))
    return _STEP_SECONDS


def window_size(default: int = 1) -> int:
    """Steps rolled into one device dispatch (`DL4J_TPU_STEP_WINDOW`).
    1 (default/unset/garbage) = the historical per-step loop."""
    return max(1, envflags.int_value(_WINDOW_GATE, default))


def place_batch(ds, put: Callable):
    """Apply `put` to every array of a DataSet/MultiDataSet (masks
    included, None passed through); non-dataset pytrees map leaf-wise."""
    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet

    def p(a):
        return None if a is None else put(a)

    if isinstance(ds, DataSet):
        return DataSet(p(ds.features), p(ds.labels),
                       p(ds.features_mask), p(ds.labels_mask))
    if isinstance(ds, MultiDataSet):
        return MultiDataSet(
            [p(f) for f in ds.features], [p(l) for l in ds.labels],
            ([p(m) for m in ds.features_masks]
             if ds.features_masks is not None else None),
            ([p(m) for m in ds.labels_masks]
             if ds.labels_masks is not None else None))
    return jax.tree_util.tree_map(put, ds)


def host_nbytes(batch) -> int:
    """Bytes of the host arrays of a DataSet/MultiDataSet (masks included)
    or of any pytree of arrays: what a `put` span hands to the runtime. An
    array that is already on a device counts nothing."""
    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet

    if isinstance(batch, DataSet):
        batch = (batch.features, batch.labels, batch.features_mask,
                 batch.labels_mask)
    elif isinstance(batch, MultiDataSet):
        batch = (batch.features, batch.labels, batch.features_masks,
                 batch.labels_masks)
    return sum(int(getattr(a, "nbytes", 0))
               for a in jax.tree_util.tree_leaves(batch)
               if not isinstance(a, jax.Array))


def dispatch_step(model, step: Callable, args,
                  scope: Callable = contextlib.nullcontext):
    """The head every per-step path shares: the next key of the model's
    rng schedule, then the jitted `step` on staged `args` (inside
    `scope()`: ParallelWrapper's ambient mesh, around the call alone).
    Parameters, state and updater state are assigned on the model; the
    device score comes back unread — `finish_step` waits for it."""
    import jax
    import jax.numpy as jnp

    model._rng, sub = jax.random.split(model._rng)
    it = jnp.asarray(model.iteration)
    with scope():
        model.params, model.state, model.opt_state, score = step(
            model.params, model.state, model.opt_state, it, sub, *args)
    return score


def finish_step(tr, model, score, batch_size: int) -> None:
    """The tail every per-step path shares, as two phases of the `step`
    span: `score_wait` (the host blocks until the device has the loss)
    and `listeners` (the `iteration_done` loop)."""
    with tr.span("score_wait", category="train"):
        model.score_ = float(score)
    model.last_batch_size = batch_size
    model.iteration += 1
    with tr.span("listeners", category="train"):
        for lst in model.listeners:
            lst.iteration_done(model, model.iteration, model.score_)


def build_window_scan(raw_step: Callable, n: int, *, watch_name: str,
                      donate_window: bool = False):
    """ONE jitted program running `n` train steps as a lax.scan.

    `raw_step(params, state, opt_state, iteration, rng, *batch_args)
    -> (params, state, opt_state, score)` is the UNJITTED single-step
    function (models expose it as `_train_step_raw`); scanning the raw
    function keeps the donation contract at this outer seam instead of
    nesting donating jits (which XLA ignores with a warning).

    The rng carry replays the host loop's exact key schedule: the fit
    paths derive each step's key as `rng, sub = jax.random.split(rng)`,
    and threefry splitting is deterministic inside or outside jit, so a
    K-window leaves `model._rng` bitwise-equal to K host splits.

    Returns `scan(params, state, opt_state, rng, it0, batch_window) ->
    (params, state, opt_state, rng, scores[n])` with the
    (params, state, opt_state, rng) carry donated. The stacked batch
    window is NOT donated by default: scan consumes xs by slicing, so
    XLA cannot alias those buffers to any output and the donation would
    only produce "donated buffers were not usable" warnings — the
    window is freed the moment Python drops it after the call anyway.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    def window_step(params, state, opt_state, rng, it0, window):
        def body(carry, batch_args):
            params, state, opt_state, rng, it = carry
            rng, sub = jax.random.split(rng)
            params, state, opt_state, score = raw_step(
                params, state, opt_state, it, sub, *batch_args)
            return (params, state, opt_state, rng, it + 1), score

        carry, scores = lax.scan(
            body, (params, state, opt_state, rng, it0), window, length=n)
        params, state, opt_state, rng, _ = carry
        return params, state, opt_state, rng, scores

    donate = (0, 1, 2, 3, 5) if donate_window else (0, 1, 2, 3)
    return jaxcompat.jit(window_step, donate_argnums=donate,
                         watch_name=watch_name)


class WindowedFitLoop:
    """The shared inner epoch loop.

    Each fit path constructs one per fit() call and hands it:

      exec_one(ds)           the path's whole step for a batch it
                             cannot stage (tbptt chunk loop, line-search
                             solver, sp/pp step): put, dispatch, score
                             and listeners inside, no look-ahead.
      stage(ds)              -> (batch_args, report_batch) with
                             batch_args the device-staged step-arg
                             pytree `(x, y, fm, lm)` (tuples for
                             ComputationGraph), placed with the sharding
                             the step uses, or None to route this batch
                             through exec_one. An array that is already
                             a `jax.Array` passes through at no cost.
      dispatch(batch_args)   -> score: the path's ONE jitted step on
                             staged args (`dispatch_step` with the
                             path's step and scope); the device score is
                             returned unread. Given with `stage`, or
                             not at all.
      raw_step               the unjitted single-step fn scanned by
                             build_window_scan; None disables windowing.
      after_dispatch(n, ds, elapsed_s)
                             optional PATH EXTRA fired once per dispatch
                             (per step at K=1), `ds` the last batch
                             staged — sampled layer spans, a worker's
                             heartbeat.
      on_dispatch()          optional hook fired immediately before
                             every dispatch of staged args, per step and
                             windowed (ParallelWrapper's chaos
                             `collective` fault point).
      dispatch_scope()       optional context manager entered around
                             the windowed scan CALL and nothing else
                             (ParallelWrapper: the ambient mesh the step
                             traces under — eager work inside that scope
                             would be placed on the mesh, so it covers
                             the jitted call only).
      place_window(window)   optional placement of the stacked window
                             pytree before the scan (ParallelWrapper
                             re-shards leaves to P(None, 'data', ...) —
                             window axis unsharded, batch axis on the
                             mesh).

    The loop owns the `etl` and `step` spans and the `put`/`dispatch`/
    `score_wait`/`listeners` of every staged batch (a batch through
    `exec_one` spans its own), window accumulation keyed on the
    batch signature (shape/dtype/mask-structure churn flushes early —
    bounded compiles, the BucketSequenceIterator contract), the scanned
    dispatch, and the per-step score replay. The per-dispatch
    attachments — the stall-watchdog beat and the HBM watermark sample —
    are ENGINE-owned: `TrainingRun.execute` binds live handles onto
    `self.health`/`self.introspection` (NULL singletons otherwise), the
    loop beats after every dispatch and, because the first K-step scan
    compile can be long enough to read as a hang, immediately BEFORE a
    windowed dispatch too (raise DL4J_TPU_STALL_TIMEOUT if a cold
    compile still trips it — docs/PERFORMANCE.md).
    """

    def __init__(self, model, *, window: Optional[int] = None,
                 raw_step: Optional[Callable] = None,
                 stage: Optional[Callable] = None,
                 dispatch: Optional[Callable] = None,
                 exec_one: Callable,
                 after_dispatch: Optional[Callable] = None,
                 on_dispatch: Optional[Callable] = None,
                 dispatch_scope: Optional[Callable] = None,
                 place_window: Optional[Callable] = None,
                 span_category: str = "train",
                 watch_prefix: str = "engine"):
        self.model = model
        self.window = window_size() if window is None else max(1, window)
        # gate-sourced windows re-read DL4J_TPU_STEP_WINDOW at each
        # epoch boundary (TrainingRun.execute), so a tuner override
        # re-keys K live through the (raw_step, n) scan cache below; an
        # explicit window= stays pinned
        self._window_from_gate = window is None
        # armed by TrainingRun.execute when the closed-loop tuner is on:
        # routes staged K=1 batches through the n=1 scan program (same
        # scores, same rng schedule) so the host dispatch tax is
        # measurable uniformly at every K, and accumulates the
        # host-overhead/step-wall signal the tuner's window rule reads
        self.tuning = False
        self._tune_host_s = 0.0
        self._tune_wall_s = 0.0
        self._tune_steps = 0
        if (stage is None) != (dispatch is None):
            raise ValueError("stage and dispatch come as a pair")
        self.raw_step = raw_step
        self.stage = stage
        self.dispatch = dispatch
        self.exec_one = exec_one
        # steps whose inputs were handed to the runtime before the
        # previous step's score was read (`fit_log()`'s `staged_ahead`)
        self.staged_ahead = 0
        self.after_dispatch = after_dispatch
        self.on_dispatch = on_dispatch
        self.dispatch_scope = dispatch_scope or contextlib.nullcontext
        self.place_window = place_window
        self.span_category = span_category
        self.watch_prefix = watch_prefix
        from deeplearning4j_tpu.telemetry import health as health_mod
        from deeplearning4j_tpu.telemetry import introspect as introspect_mod

        # engine-owned per-dispatch attachments; TrainingRun.execute
        # swaps in the live handles for the duration of the fit
        self.health = health_mod.NULL_HEALTH
        self.introspection = introspect_mod.NULL_FIT
        self._buf: List[Tuple[PyTree, int]] = []
        self._buf_sig = None
        # scan-program cache ON THE MODEL, keyed (raw_step, n): fit()
        # builds a fresh loop per call, so a per-loop cache would
        # recompile the K-step program every fit (fit2+resume+fit2 would
        # pay the big scan compile three times); keying on the raw step
        # identity invalidates naturally when the train step is rebuilt
        self._scans: Dict[Tuple[Callable, int], Callable] = (
            model.__dict__.setdefault("_window_scan_cache", {}))

    @property
    def windowed(self) -> bool:
        return ((self.window > 1 or self.tuning)
                and self.raw_step is not None
                and self.stage is not None)

    def tuning_signals(self) -> Dict[str, float]:
        """Per-step means accumulated since the last call (one epoch at
        the engine's tick cadence), then reset: ``host_overhead_ms`` —
        window stacking + jit dispatch-call-return tax, the host work a
        wider K amortizes — and ``step_ms`` — full per-step wall
        including the device sync. Empty when nothing was measured
        (tuning off, or every batch took the fallback path)."""
        n = self._tune_steps
        if not n:
            return {}
        sig = {"host_overhead_ms": self._tune_host_s * 1e3 / n,
               "step_ms": self._tune_wall_s * 1e3 / n,
               "window": self.window, "steps": n}
        self._tune_host_s = self._tune_wall_s = 0.0
        self._tune_steps = 0
        return sig

    # ------------------------------------------------------------------
    def run_epoch(self, batches) -> None:
        """One pass over `batches` (any iterable of DataSet/MultiDataSet);
        flushes the pending window before returning, so epoch-end hooks
        (listeners, checkpoints) always see every step applied. While
        telemetry is on, the epoch runs under a fit-level TraceContext
        (telemetry/context.py) — every etl/step span it emits shares one
        trace_id — unless the caller (a distributed master) already
        attached one, in which case the steps join that trace."""
        from deeplearning4j_tpu.telemetry import context as context_mod

        tr = trace_mod.tracer()
        token = None
        if tr.enabled and context_mod.current() is None:
            token = context_mod.attach(context_mod.new_trace())
        try:
            # the `etl` span is open WHILE the iterator works, so the
            # profiler sees the wait where it happens
            source = tr.spanned("etl", batches, category="data")
            if self.windowed:
                self._run_windowed(source, tr)
            else:
                self._run_ahead(source, tr)
        finally:
            if token is not None:
                context_mod.detach(token)

    # ------------------------------------------------------------------
    # K=1: one dispatch a step, one batch of look-ahead
    # ------------------------------------------------------------------
    def _run_ahead(self, source, tr) -> None:
        """At most ONE staged, undispatched batch exists at any time: it
        is this frame's `nxt`, so an epoch that unwinds (a listener's
        exception, a chaos fault) drops it without dispatching it — it
        was never applied, and a resumed fit replays the epoch from its
        checkpoint."""
        nxt = self._take(source, tr)
        while nxt is not None:
            ds, staged, etl_ms = nxt
            self.model.last_etl_time_ms = etl_ms
            if staged is None:
                # a batch kind the path cannot stage: its whole step, in
                # order, and nothing is taken ahead of it
                self._exec_fallback(ds, tr)
                nxt = self._take(source, tr)
            else:
                nxt = self._exec_staged(ds, staged, source, tr)

    def _take(self, source, tr):
        """The next batch off the iterator (`etl`) and into the runtime's
        hands (`put`): (ds, staged or None, etl ms), or None at the end."""
        t0 = time.perf_counter()
        ds = next(source, _END)
        if ds is _END:
            return None
        etl_ms = (time.perf_counter() - t0) * 1e3
        staged = (self._stage_spanned(ds, tr)
                  if self.stage is not None else None)
        return ds, staged, etl_ms

    def _stage_spanned(self, ds, tr):
        """`stage(ds)` under a `put` span that carries the batch's host
        bytes; None (and no span) for a batch the path cannot stage —
        exec_one makes, and spans, its own put."""
        with tr.span("put", category=self.span_category,
                     bytes=host_nbytes(ds)) as sp:
            staged = self.stage(ds)
            if staged is None:
                sp.discard()
        return staged

    def _exec_staged(self, ds, staged, source, tr):
        """Step k on staged args; returns batch k+1 as `_take` gives it.
        Between the jitted call's return and the wait for its score the
        thread takes batch k+1 and hands it to the runtime, so that
        transfer overlaps the chip's work on step k. `iteration_done(k)`
        still runs before step k+1 is dispatched."""
        args, report_batch = staged
        m = self.model
        t_step = time.perf_counter()
        with tr.step_span("step", m.iteration,
                          category=self.span_category) as sp:
            if self.on_dispatch is not None:
                self.on_dispatch()
            with tr.span("dispatch", category=self.span_category):
                score = self.dispatch(args)
            t_take = time.perf_counter()
            try:
                nxt = self._take(source, tr)
            except Exception:
                # the iterator (or the put) failed AFTER step k was
                # dispatched, so step k is finished first, as a loop
                # that reads the iterator only between steps would
                # have, and then the error goes on. An interrupt
                # (BaseException) unwinds at once, past the listeners
                finish_step(tr, m, score, report_batch)
                raise
            # batch k+1's etl and put are not step k's time: the `step`
            # record, the step histogram and `elapsed` stay what a step
            # costs, the yardstick input_verdict() holds `etl` against
            ahead_s = time.perf_counter() - t_take
            sp.exclude(ahead_s)
            finish_step(tr, m, score, report_batch)
            if nxt is not None and nxt[1] is not None:
                self.staged_ahead += 1
        self._step_done(ds, time.perf_counter() - t_step - ahead_s, tr)
        return nxt

    # ------------------------------------------------------------------
    # K>1: signature-keyed windows
    # ------------------------------------------------------------------
    def _run_windowed(self, source, tr) -> None:
        t0 = time.perf_counter()
        try:
            for ds in source:
                self.model.last_etl_time_ms = (
                    time.perf_counter() - t0) * 1e3
                self._consume(ds, tr)
                t0 = time.perf_counter()
        except BaseException:
            # a chaos fault / preemption mid-epoch: drop the staged-
            # but-undispatched batches (they were never applied — a
            # resumed fit replays the epoch from its checkpoint)
            # rather than dispatching device work during exception
            # unwind
            self._buf = []
            raise
        self.flush(tr)

    def _consume(self, ds, tr) -> None:
        staged = self._stage_spanned(ds, tr)
        if staged is None:
            # incompatible batch kind (tbptt chunk / solver / sp / pp):
            # apply the pending window first so step ORDER is preserved
            self.flush(tr)
            self._exec_fallback(ds, tr)
            return
        args, report_batch = staged
        sig = _signature(args)
        if self._buf and sig != self._buf_sig:
            # shape/dtype/mask-structure churn: dispatch what we have
            self.flush(tr)
        self._buf.append((args, report_batch))
        self._buf_sig = sig
        self._last_ds = ds
        if len(self._buf) >= self.window:
            self.flush(tr)

    def _exec_fallback(self, ds, tr) -> None:
        t_step = time.perf_counter()
        with tr.step_span("step", self.model.iteration,
                          category=self.span_category):
            self.exec_one(ds)
        self._step_done(ds, time.perf_counter() - t_step, tr)

    def _step_done(self, ds, elapsed, tr) -> None:
        if tr.enabled:
            _step_hist().observe(elapsed)
        self._post_dispatch(1, ds, elapsed)

    def _post_dispatch(self, n, ds, elapsed) -> None:
        """Once per dispatch (per step at K=1): the path extra first
        (layer spans, a worker's beat), then the engine-owned watermark
        sample and watchdog beat."""
        if self.after_dispatch is not None:
            self.after_dispatch(n, ds, elapsed)
        self.introspection.after_step()
        self.health.beat(self.model.iteration)

    # ------------------------------------------------------------------
    def flush(self, tr=None) -> None:
        """Dispatch the pending window (no-op when empty). Tail windows
        (epoch end / signature churn) scan at their actual length — one
        extra executable per distinct tail, bounded by the window size."""
        if not self._buf:
            return
        if tr is None:
            tr = trace_mod.tracer()
        batch, self._buf = self._buf, []
        n = len(batch)
        m = self.model
        # listeners that snapshot state (DivergenceSentry) grab the clean
        # pre-window params here — inside the burst below, m.params is
        # already the window-end state
        for lst in m.listeners:
            cb = getattr(lst, "on_window_start", None)
            if cb is not None:
                cb(m)
        # beat BEFORE the windowed dispatch: the first K-step scan
        # compile can be long, and a silent compile must not trip the
        # stall watchdog
        self.health.beat(m.iteration)
        if self.on_dispatch is not None:
            self.on_dispatch()
        import jax
        import jax.numpy as jnp

        t_host0 = time.perf_counter()
        # the staged batches are on the device already (their `put` spans
        # carry the bytes); this one is the stack and the re-placement
        with tr.span("put", category=self.span_category):
            window = jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves), *[a for a, _ in batch])
            if self.place_window is not None:
                window = self.place_window(window)
        scan = self._scans.get((self.raw_step, n))
        cold = scan is None
        if cold:
            scan = self._scans[(self.raw_step, n)] = build_window_scan(
                self.raw_step, n,
                watch_name=f"{self.watch_prefix}.window_step[{n}]")
        t_step = time.perf_counter()
        with tr.span("dispatch", category=self.span_category):
            it0 = jnp.asarray(m.iteration)
            with self.dispatch_scope():
                m.params, m.state, m.opt_state, m._rng, scores = scan(
                    m.params, m.state, m.opt_state, m._rng, it0, window)
        # the jitted call returned (async dispatch enqueued): everything
        # up to here — window stacking, placement, cache lookup, jit
        # call/trace — is HOST work a wider window amortizes; the sync
        # below is where device time is paid
        t_call = time.perf_counter()
        # ONE host sync per window (vs one float(score) per step)
        with tr.span("score_wait", category=self.span_category):
            scores = np.asarray(scores)
        elapsed = time.perf_counter() - t_step
        if self.tuning and not cold:
            # cold dispatches carry the scan COMPILE in the call-return
            # time; feeding that to the tuner would read one-off XLA
            # work as steady-state host tax and widen K spuriously
            self._tune_host_s += t_call - t_host0
            self._tune_wall_s += time.perf_counter() - t_host0
            self._tune_steps += n
        if tr.enabled:
            # n duration-accurate per-step spans, so step-span medians
            # (MFU accounting, input_verdict) stay per-step comparable
            per_step_ms = elapsed * 1e3 / n
            hist = _step_hist()
            for _ in range(n):
                tr.add_span("step", per_step_ms, category=self.span_category)
                hist.observe(per_step_ms / 1e3)
        # during the burst m.params already hold the WINDOW-END state
        # while m.iteration walks through mid-window values — listeners
        # that persist (iteration, params) pairs (CheckpointListener)
        # consult this flag and defer to on_window_end, where the pair
        # is consistent again
        with tr.span("listeners", category=self.span_category):
            self._replay(batch, scores)
        self._post_dispatch(n, getattr(self, "_last_ds", None), elapsed)


    def _replay(self, batch, scores) -> None:
        """The window's scores through `iteration_done`, one step at a
        time, then `on_window_end`."""
        m = self.model
        m._window_replay = True
        try:
            it_expected = m.iteration
            for (_, report_batch), s in zip(batch, scores):
                m.score_ = float(s)  # jaxlint: disable=JX010 — s is a host numpy scalar; the one device sync is the np.asarray in flush
                m.last_batch_size = report_batch
                m.iteration += 1
                it_expected += 1
                for lst in m.listeners:
                    lst.iteration_done(m, m.iteration, m.score_)
                if m.iteration != it_expected:
                    # a listener REWOUND the model (sentry snapshot/
                    # checkpoint restore): the burst's remaining scores
                    # describe discarded steps per-step mode never
                    # computes — replaying them would advance the
                    # counter past the restored params and feed ghost
                    # iterations to every listener
                    break
        finally:
            m._window_replay = False
        for lst in m.listeners:
            cb = getattr(lst, "on_window_end", None)
            if cb is not None:
                cb(m)


def _signature(args) -> tuple:
    """Hashable (treedef, shapes, dtypes) key deciding window
    compatibility — batches scan together only when they trace
    identically (same pytree structure incl. None masks, same
    shapes/dtypes)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (treedef,
            tuple((tuple(l.shape), str(l.dtype)) for l in leaves))


def scan_carry_specs(model):
    """(in_specs, out_specs) for the window scan's param carry, or None
    when the model carries no fsdp layout.

    The window scan carries params through K steps under the layout's
    sharded-at-rest specs (`FsdpArrangement.specs`); each step gathers
    on use and the updated params re-enter the next iteration, where the
    layout would place them at `extend(drop_fsdp(spec))`. A stable scan
    needs those to be the same tree — shardlint's `audit_scan_carry`
    (DLA018) checks exactly that fixed point on a BUILT model, the
    runtime half of the static round-trip analyze_sharding performs on
    the config."""
    import jax

    from deeplearning4j_tpu.parallel import layout as layout_mod

    fsdp = getattr(model, "_fsdp_layout", None)
    params = getattr(model, "params", None)
    if fsdp is None or not params:
        return None
    layout = layout_mod.DEFAULT_LAYOUT
    fsdp_size = fsdp.mesh.shape.get(layout.fsdp_axis, 1)
    in_specs = {}
    out_specs = {}
    for key, spec_tree in fsdp.specs.items():
        sub = params.get(key)
        if sub is None:
            continue
        in_specs[key] = spec_tree
        out_specs[key] = jax.tree_util.tree_map(
            lambda s, p: layout.extend(
                layout.drop_fsdp(s), np.shape(p), fsdp_size),
            spec_tree, sub)
    return in_specs, out_specs


# ---------------------------------------------------------------------------
# the engine-owned outer fit lifecycle
# ---------------------------------------------------------------------------

_ATTACHMENTS = ("checkpoint_manager",)


class TrainingRun:
    """THE fit lifecycle, shared by every fit path.

    Owns everything the three facades used to wire by hand:

      - resume/save cadence: `checkpoint_manager=` (the
        resilience.CheckpointManager keyword every fit() forwards here
        via `**attachments`) restores the newest valid checkpoint at
        construction — BEFORE the facade builds steps or places params
        on a mesh — and writes an atomic checkpoint at each epoch end;
        `epochs` counts the TOTAL target, so a run killed after epoch 2
        of epochs=4 resumes and trains exactly 2 more
        (docs/RESILIENCE.md). A diverged state is never checkpointed —
        a NaN checkpoint would become the "last good" one rollback
        restores.
      - the stall-watchdog heartbeat + HBM watermark tracker (NULL
        singletons when telemetry is off), bound onto the loop for the
        duration of `execute`.
      - the fit-level TraceContext, attached OUTSIDE the crash guard so
        the record_crash bundle still sees the active trace and stamps
        its trace_id (the `postmortem --trace` join).
      - TrainingListener firing order: on_fit_start, per-epoch
        on_epoch_start/end around the inner loop, on_fit_end in the
        finally (swallow=True — it fires even when the loop dies).
      - the crash-path flight bundle (record_crash with the fit phase),
        plus an optional `cleanup_on_crash` (ParallelWrapper shuts its
        prefetch producer down before re-raising).
    """

    def __init__(self, model, phase: str, *, epochs: int = 1,
                 **attachments):
        unknown = sorted(set(attachments) - set(_ATTACHMENTS))
        if unknown:
            raise TypeError(
                f"fit() got unexpected keyword argument(s): {unknown}; "
                f"engine attachments are {list(_ATTACHMENTS)}")
        # every fit compiles through the one placed cache directory
        # (util/compile_cache.py), so a second process on the same
        # machine reads the step back instead of recompiling it
        compile_cache.ensure()
        self.model = model
        self.phase = phase
        self.manager = attachments.get("checkpoint_manager")
        if self.manager is not None:
            self.manager.restore_into(model)
            epochs = max(0, epochs - model.epoch)
        self.epochs = epochs

    def save_epoch(self) -> None:
        """Epoch-end checkpoint cadence (no-op without a manager)."""
        if self.manager is not None and np.isfinite(self.model.score_):
            self.manager.save(self.model, extra={"trigger": "epoch"})

    def execute(self, loop: "WindowedFitLoop", batches, *,
                cleanup_on_crash: Optional[Callable] = None):
        """Run the full fit: `batches` is the epoch's iterable, or a
        zero-arg callable producing one (a fresh iterator per epoch —
        ComputationGraph's shape)."""
        from deeplearning4j_tpu.optimize.listeners import fire_lifecycle
        from deeplearning4j_tpu.telemetry import context as context_mod
        from deeplearning4j_tpu.telemetry import flight as flight_mod
        from deeplearning4j_tpu.telemetry import health as health_mod
        from deeplearning4j_tpu.telemetry import introspect as introspect_mod
        from deeplearning4j_tpu.telemetry import tuner as tuner_mod

        m = self.model
        # the always-on accounts of this fit (telemetry.fit_log()): what
        # the spans and JAX's compile events add between here and the end
        account = trace_mod.tracer().account
        watcher = introspect_mod.watcher()
        phases0, compile0 = account.mark(), watcher.account.mark()
        counters0 = counters_mod.begin(m)
        iteration0, t_fit0 = m.iteration, time.perf_counter()
        ahead0 = loop.staged_ahead
        hb = health_mod.fit_health(self.phase)
        fi = introspect_mod.fit_introspection(m)
        loop.health, loop.introspection = hb, fi
        # closed-loop tuning (DL4J_TPU_AUTOTUNE): arm the loop's signal
        # accumulation; ticks fire at each epoch END below. None when
        # the gate is off — no tuner state exists (docs/TUNING.md)
        tn = tuner_mod.tuner()
        loop.tuning = tn is not None
        ctx_token = (context_mod.attach(context_mod.new_trace())
                     if trace_mod.tracer().enabled
                     and context_mod.current() is None else None)
        fire_lifecycle(m.listeners, "on_fit_start", m)
        try:
            for _ in range(self.epochs):
                for lst in m.listeners:
                    lst.on_epoch_start(m, m.epoch)
                loop.run_epoch(batches() if callable(batches) else batches)
                for lst in m.listeners:
                    lst.on_epoch_end(m, m.epoch)
                m.epoch += 1
                self.save_epoch()
                if tn is not None:
                    # the epoch boundary IS the tick: the tuner sees
                    # this epoch's measured signals, and any K override
                    # it (or the SLO gate's revert) installs re-keys the
                    # window scan below — the next epoch dispatches
                    # through the (raw_step, n) cache at the new K
                    tn.tick(signals=loop.tuning_signals(),
                            source="epoch")
                    if loop._window_from_gate:
                        loop.window = window_size()
        except BaseException as e:
            # black-box dump while the dying state is still inspectable
            # (no-op with telemetry off; never raises)
            flight_mod.record_crash(e, model=m,
                                    checkpoint_manager=self.manager,
                                    phase=self.phase)
            if cleanup_on_crash is not None:
                cleanup_on_crash()
            raise
        finally:
            # on_fit_end fires even when the loop dies (chaos/
            # preemption): listeners flush open traces/files
            # deterministically
            hb.end()
            fi.end(m)
            loop.health = health_mod.NULL_HEALTH
            loop.introspection = introspect_mod.NULL_FIT
            fire_lifecycle(m.listeners, "on_fit_end", m, swallow=True)
            if ctx_token is not None:
                context_mod.detach(ctx_token)
            compiled = watcher.account.claim("fit", compile0)
            entry = {
                "path": self.phase,
                "steps": m.iteration - iteration0,
                "staged_ahead": loop.staged_ahead - ahead0,
                "t_start_s": trace_mod.since_import(t_fit0),
                "wall_s": time.perf_counter() - t_fit0,
                "compiles": compiled["backend_compiles"],
                "compile": compiled,
                "phases": account.since(phases0)}
            entry.update(counters_mod.end(m, counters0))
            trace_mod.record_fit(entry)
        return m


def run_partition(model, batches, *, beat: Optional[Callable] = None) -> int:
    """A distributed worker's shard, through the model's OWN engine loop
    (`model._engine_loop()`) instead of a private per-batch split loop —
    the window gate, etl/step spans and signature-keyed accumulation
    apply to worker replicas exactly as to fit(). `beat` (the membership
    heartbeat — the liveness signal the missed-heartbeat detector
    watches) fires once per dispatch, which at the K=1 default is once
    per batch, the historical cadence. Returns the batch count.

    Models without engine-loop wiring (imported/custom nets) fall back
    to one fit() per batch, the historical worker fallback."""
    wiring = getattr(model, "_engine_loop", None)
    if wiring is None:
        n = 0
        for ds in batches:
            model.fit(ds)
            n += 1
            if beat is not None:
                beat()
        return n

    n = 0

    def counted():
        nonlocal n
        for ds in batches:
            n += 1
            yield ds

    def after(k, ds, elapsed):
        if beat is not None:
            beat()

    wiring(after_dispatch=after).run_epoch(counted())
    return n


@contextlib.contextmanager
def master_session(model, phase: str, registry=None,
                   barrier_checkpoints=None):
    """The distributed masters' fit lifecycle, hoisted: the master-level
    stall-watchdog heartbeat (an eviction/rebalance makes PROGRESS and
    must never read as a hang), the fit-level TraceContext shared with
    the membership registry (every split dispatch, worker fit and
    membership transition joins ONE trace_id — docs/TELEMETRY.md), and
    the registry's flight-bundle context (cleared on exit so the
    long-lived registry never pins the param trees between fits).
    Yields the heartbeat handle."""
    from deeplearning4j_tpu.telemetry import context as context_mod
    from deeplearning4j_tpu.telemetry import health as health_mod

    if registry is not None:
        registry.set_flight_context(model, barrier_checkpoints)
    hb = health_mod.fit_health(phase)
    fit_token = None
    if trace_mod.tracer().enabled:
        fit_ctx = context_mod.new_trace()
        fit_token = context_mod.attach(fit_ctx)
        if registry is not None:
            registry.set_trace_context(fit_ctx)
    try:
        yield hb
    finally:
        hb.end()
        if fit_token is not None:
            context_mod.detach(fit_token)
            if registry is not None:
                registry.set_trace_context(None)
        if registry is not None:
            registry.set_flight_context(None, barrier_checkpoints)
