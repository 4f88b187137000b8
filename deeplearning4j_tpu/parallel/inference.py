"""ParallelInference — multi-device inference server with dynamic batching.

Reference: parallelism/ParallelInference.java:401 — INSTANT mode (each request
dispatched immediately) vs BATCHED mode (ObservablesProvider coalesces
requests up to batch_limit before dispatch, :52-140), worker threads pinned
per device.

TPU-native: one jitted forward over the data-axis mesh replaces per-device
model replicas; dynamic batching coalesces host requests into one sharded
batch. Thread-safe: a single background dispatcher thread owns the device.

Two dispatchers behind one API:

  * With the `DL4J_TPU_SERVING` gate ON, construction routes through the
    overload-hardened serving runtime (serving/runtime.py): bucketed
    padded shapes, admission control with per-request deadlines, bounded
    queue with load shedding, circuit breaking, drain-on-shutdown, full
    telemetry. `output(x, deadline_s=...)` raises the typed
    serving.errors on refusal. See docs/SERVING.md.
  * With the gate OFF (default) the historical lightweight dispatcher
    runs — no buckets, no breaker, no serving metrics, nothing extra
    allocated (tier-1 asserted) — but with its liveness bugs fixed: the
    queue drains on shutdown and every pending request resolves with a
    typed error (ShutdownError / DispatcherCrashedError), `output()`
    waits in bounded slices keyed to an optional deadline instead of
    parking forever (jaxlint JX012), coalescing never overshoots
    `batch_limit` (an oversize request dispatches alone), and requests
    only coalesce with matching trailing shape + dtype so a
    mismatched-rank input fails alone instead of poisoning the batch.

Both modes guarantee: no caller ever blocks forever.
"""
from __future__ import annotations

import logging
import queue
import threading
import time
from typing import List, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel import mesh as mesh_mod
from deeplearning4j_tpu.resilience.retry import Deadline
from deeplearning4j_tpu.serving.buckets import signature as _sig
from deeplearning4j_tpu.serving.errors import (
    DeadlineExceededError,
    DispatcherCrashedError,
    ShutdownError,
)
from deeplearning4j_tpu.telemetry import context as context_mod
from deeplearning4j_tpu.telemetry import trace as trace_mod
from deeplearning4j_tpu.util import envflags

logger = logging.getLogger("deeplearning4j_tpu")

_SERVING_GATE = "DL4J_TPU_SERVING"


class _Request:
    def __init__(self, x, deadline: Optional[Deadline] = None):
        self.x = x
        self.deadline = deadline or Deadline(None)
        self.event = threading.Event()
        self.result: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None
        # per-request TraceContext while telemetry is on (None otherwise);
        # the dispatcher attaches it so the dispatch span joins the
        # request's trace across the thread handoff
        self.ctx = None


class ParallelInference:
    INSTANT = "instant"
    BATCHED = "batched"

    def __init__(self, model, mesh=None, mode: str = "batched",
                 batch_limit: int = 32, queue_limit: int = 64,
                 wait_ms: float = 2.0, workers: Optional[int] = None):
        self.model = model
        self.mesh = mesh or mesh_mod.build_mesh(
            mesh_mod.MeshSpec.data_parallel(workers or len(jax.devices()))
        )
        self.mode = mode
        self.batch_limit = batch_limit
        self.wait_ms = wait_ms
        self._serving = None
        if envflags.enabled(_SERVING_GATE, False):
            # the serving runtime owns everything from here: buckets,
            # deadlines, shedding, breaker, drain. Imported only on this
            # branch — the gate-off path allocates no serving state.
            from deeplearning4j_tpu.serving.runtime import InferenceServer

            self._serving = InferenceServer(
                model=model, mesh=self.mesh, batch_limit=batch_limit,
                queue_limit=queue_limit,
                wait_ms=(0.0 if mode == self.INSTANT else wait_ms),
                name="ParallelInference")
            return
        self._q: "queue.Queue[_Request]" = queue.Queue(maxsize=queue_limit)
        self._carry: Optional[_Request] = None
        self._crash: Optional[BaseException] = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._dispatch_loop,
                                        daemon=True,
                                        name="ParallelInference-dispatch")
        self._thread.start()

    # ------------------------------------------------------------------
    def output(self, x, deadline_s: Optional[float] = None) -> np.ndarray:
        """Blocking inference call, thread-safe (the reference's
        ParallelInference.output). `deadline_s` bounds the WHOLE call;
        on expiry DeadlineExceededError is raised instead of waiting
        further. Even without a deadline the wait is sliced: a dead or
        shut-down dispatcher surfaces as a typed error, never a hang."""
        if self._serving is not None:
            return self._serving.output(x, deadline_s=deadline_s)
        self._check_live()
        deadline = Deadline(deadline_s)
        req = _Request(np.asarray(x), deadline)
        tr = trace_mod.tracer()
        if not tr.enabled:
            return self._await(req, deadline)
        req.ctx = context_mod.new_trace()
        with context_mod.activate(req.ctx):
            t0 = time.perf_counter()
            outcome = "ok"
            try:
                tr.add_flow("inference.batch", flow_id=req.ctx.trace_id,
                            phase="s", category="serving")
                return self._await(req, deadline)
            except BaseException as e:
                outcome = type(e).__name__
                raise
            finally:
                tr.add_span("inference.resolve",
                            (time.perf_counter() - t0) * 1e3,
                            category="serving", outcome=outcome)

    def _await(self, req: _Request, deadline: Deadline) -> np.ndarray:
        while True:  # bounded enqueue: a full queue must not park us past
            self._check_live()  # the deadline or a dispatcher death
            if deadline.expired:
                raise DeadlineExceededError(
                    f"deadline {deadline.seconds:.3g}s expired while "
                    f"waiting for queue space")
            try:
                self._q.put(req, timeout=0.05)
                break
            except queue.Full:
                continue
        while not req.event.wait(0.05):
            if req.event.is_set():
                break
            if deadline.expired:
                raise DeadlineExceededError(
                    f"deadline {deadline.seconds:.3g}s expired awaiting "
                    f"dispatch")
            if self._crash is not None:
                raise DispatcherCrashedError(
                    f"inference dispatcher died: {self._crash!r}",
                    cause=self._crash)
            if not self._thread.is_alive():
                # drain resolves queued requests; this catches a request
                # racing a death that never reached the drain
                raise DispatcherCrashedError(
                    "inference dispatcher thread is dead")
        if req.error is not None:
            raise req.error
        return req.result

    def _check_live(self) -> None:
        if self._crash is not None:
            raise DispatcherCrashedError(
                f"inference dispatcher died: {self._crash!r}",
                cause=self._crash)
        if self._stop.is_set():
            raise ShutdownError("ParallelInference is shut down")

    def shutdown(self):
        """Stop the dispatcher AND drain: every queued request resolves
        with ShutdownError — no caller is left parked on a dead queue."""
        if self._serving is not None:
            return self._serving.shutdown()
        self._stop.set()
        dl = Deadline(5.0)
        while self._thread.is_alive() and not dl.expired:
            self._thread.join(0.1)
        # belt: the loop's exit path drains too, but a thread that died
        # before setting _crash (or a request enqueued mid-stop) must
        # still resolve
        self._drain(ShutdownError("ParallelInference is shut down"))

    # ------------------------------------------------------------------
    def _take_next(self, timeout: float) -> Optional[_Request]:
        """Next live request (carry slot first). A request whose deadline
        already expired is resolved here and never dispatched — its
        caller raised and walked away, and doing the device work anyway
        would burn batch capacity exactly when overload made deadlines
        expire in the first place."""
        while True:
            if self._carry is not None:
                nxt, self._carry = self._carry, None
            else:
                try:
                    nxt = self._q.get(timeout=timeout)
                except queue.Empty:
                    return None
            if not nxt.deadline.expired:
                return nxt
            nxt.error = DeadlineExceededError(
                f"deadline {nxt.deadline.seconds:.3g}s expired in queue")
            nxt.event.set()
            timeout = 0.0  # expired ones are free; don't re-wait

    def _drain(self, error: BaseException) -> None:
        if self._carry is not None:
            self._carry.error = error
            self._carry.event.set()
            self._carry = None
        while True:
            try:
                r = self._q.get_nowait()
            except queue.Empty:
                break
            r.error = error
            r.event.set()

    def _dispatch_loop(self):
        tr = trace_mod.tracer()
        if tr.enabled:  # name the lane so Chrome/Perfetto shows it
            tr.set_thread_name(threading.get_ident(),
                               "ParallelInference-dispatch")
        try:
            self._pump()
        except BaseException as e:  # surface to callers, never vanish
            self._crash = e
            logger.exception("ParallelInference dispatcher crashed")
            self._drain(DispatcherCrashedError(
                f"inference dispatcher died: {e!r}", cause=e))
        else:
            self._drain(ShutdownError("ParallelInference is shut down"))

    def _pump(self):
        while not self._stop.is_set():
            first = self._take_next(timeout=0.1)
            if first is None:
                continue
            batch = [first]
            total = first.x.shape[0]
            sig = _sig(first.x)
            if self.mode == self.BATCHED:
                wait = self.wait_ms / 1000.0
                # never overshoot batch_limit: a request that would is
                # carried into the NEXT batch (an oversize single
                # request — total already past the limit — dispatches
                # alone). Mismatched trailing shape/dtype also carries:
                # it must fail alone, not poison this batch.
                while total < self.batch_limit:
                    nxt = self._take_next(timeout=wait)
                    if nxt is None:
                        break
                    if (_sig(nxt.x) != sig
                            or total + nxt.x.shape[0] > self.batch_limit):
                        self._carry = nxt
                        break
                    batch.append(nxt)
                    total += nxt.x.shape[0]
            self._run_batch(batch)

    def _run_batch(self, batch: List[_Request]):
        t0 = time.perf_counter()
        try:
            sizes = [r.x.shape[0] for r in batch]
            x = (np.concatenate([r.x for r in batch], axis=0)
                 if len(batch) > 1 else batch[0].x)
            n_data = self.mesh.shape["data"]
            pad = (-x.shape[0]) % n_data
            if pad:
                x = np.concatenate([x, np.repeat(x[-1:], pad, axis=0)],
                                   axis=0)
            sh = NamedSharding(self.mesh, P("data", *([None] * (x.ndim - 1))))  # jaxlint: disable=JX018 — input staging (batch split), not a param placement
            xd = jax.device_put(x, sh)
            with jax.set_mesh(self.mesh):  # kernels run per batch shard
                out = np.asarray(self.model.output(xd))
            if pad:
                out = out[: out.shape[0] - pad]
            off = 0
            for r, s in zip(batch, sizes):
                r.result = out[off : off + s]
                off += s
                r.event.set()
            self._trace_batch(batch, (time.perf_counter() - t0) * 1e3, "ok")
        except BaseException as e:
            self._trace_batch(batch, (time.perf_counter() - t0) * 1e3,
                              type(e).__name__)
            for r in batch:
                r.error = e
                r.event.set()

    def _trace_batch(self, batch: List[_Request], dt_ms: float,
                     outcome: str) -> None:
        """Per-member dispatch spans on the dispatcher lane, each stamped
        with its request's trace ids; the flow finish binds the span back
        to the caller-side `inference.batch` arrow started in output()."""
        tr = trace_mod.tracer()
        if not tr.enabled:
            return
        for r in batch:
            if r.ctx is None:
                continue
            with context_mod.activate(r.ctx):
                tr.add_flow("inference.batch", flow_id=r.ctx.trace_id,
                            phase="f", category="serving")
                tr.add_span("inference.dispatch", dt_ms, category="serving",
                            rows=r.x.shape[0], batch_size=len(batch),
                            outcome=outcome)
