"""InferenceServer — overload-hardened continuous-batching serving.

The production successor to `parallel/inference.py`'s dispatcher
(PAPER.md layer 4: DL4J's ParallelInference; PAPERS.md 1605.08695 /
1603.04467: TF-Serving's batching + fault-tolerance posture). One
background dispatcher thread owns the device; callers submit requests
that are coalesced into bucketed padded batches (serving/buckets.py) and
dispatched through one jitted forward — and EVERY way that can go wrong
under heavy traffic is a typed, bounded outcome instead of an unbounded
queue or a hung caller:

  admission control   a request whose deadline (resilience/retry.py
                      Deadline) would expire before its bucket could
                      dispatch — estimated from the coalesce window plus
                      an EMA of recent dispatch latency scaled by queue
                      depth — is rejected at submit with
                      DeadlineExceededError rather than queued to die.
  load shedding       the queue is bounded; past `queue_limit` the
                      configured policy sheds: `reject_newest` (refuse
                      the submit with ShedError + retry-after hint) or
                      `drop_oldest` (resolve the oldest queued request
                      with ShedError to admit the newer). The hint is
                      floored by the breaker's cooldown remaining when
                      the circuit is open, so retrying clients back off
                      past the open window. Every shed ticks
                      ``dl4j_tpu_serving_shed_total{reason}``.
  tenant isolation    with a `tenancy=` TenancyController
                      (serving/tenancy.py), per-tenant token buckets run
                      in front of the shared queue (an over-quota tenant
                      sheds ITSELF with TenantQuotaError, reason
                      `tenant_quota`) and the queue drains by deficit
                      round-robin across tenant sub-queues at coalesce
                      time, so one tenant's backlog cannot starve
                      another's p99.
  circuit breaking    consecutive dispatch failures or non-finite
                      outputs (the DivergenceSentry's check applied to
                      inference — resilience/sentry.py tree_all_finite)
                      open the breaker (serving/breaker.py): requests
                      are rejected FAST with CircuitOpenError while
                      half-open probes test recovery. Opening writes a
                      flight-recorder bundle (reason "serving_breaker").
  drain on shutdown   shutdown() completes the in-flight batch, resolves
                      every queued request with ShutdownError, and a
                      dispatcher crash resolves queued + future requests
                      with DispatcherCrashedError. No caller ever blocks
                      forever: output() waits in bounded slices, keyed
                      to its deadline (the dynamic twin of jaxlint
                      JX012).

Chaos fault points (resilience/chaos.py grammar, e.g.
``DL4J_TPU_CHAOS=serving_dispatch@1:2:3``):

    serving_dispatch  the batch dispatch raises ChaosError
    serving_slow      SILENT: dispatch sleeps `slow_fault_s` first (the
                      deadline-expiry / tail-latency arc)
    serving_nan       SILENT: outputs replaced with NaN (the
                      non-finite -> breaker arc)

Telemetry (all on the existing core, docs/TELEMETRY.md):
``dl4j_tpu_serving_latency_seconds`` (histogram, queue wait + dispatch),
``dl4j_tpu_serving_latency_{p50,p99}_seconds`` gauges over the last 512
requests, ``dl4j_tpu_serving_queue_depth``,
``dl4j_tpu_serving_shed_total{reason}``,
``dl4j_tpu_serving_requests_total{outcome}``,
``dl4j_tpu_serving_breaker_transitions_total{state}`` (breaker.py), a
``serving.dispatch`` span per batch, and breaker + queue state on
``/healthz`` via `healthz_section()` (503 while open — ui/server.py).

Gate: `DL4J_TPU_SERVING` routes ParallelInference through this runtime;
constructing an InferenceServer directly always works. The disabled path
allocates nothing (parallel/inference.py never imports this module with
the gate off — tier-1 asserted). Config gates, all read at construction
through util/envflags.py: DL4J_TPU_SERVING_SHED (reject_newest |
drop_oldest), DL4J_TPU_SERVING_DEADLINE (default per-request deadline
seconds; 0/unset = none), DL4J_TPU_SERVING_BREAK_AFTER (5),
DL4J_TPU_SERVING_COOLDOWN (1.0 s), DL4J_TPU_SERVING_PROBES (2).
"""
from __future__ import annotations

import logging
import threading
import time
import weakref
from collections import deque
from typing import Callable, List, Optional

import numpy as np

from deeplearning4j_tpu.resilience import chaos
from deeplearning4j_tpu.resilience.retry import Deadline
from deeplearning4j_tpu.serving import buckets as buckets_mod
from deeplearning4j_tpu.serving.breaker import CircuitBreaker, OPEN
from deeplearning4j_tpu.serving.errors import (
    CircuitOpenError,
    DeadlineExceededError,
    DispatchFailedError,
    DispatcherCrashedError,
    NonFiniteOutputError,
    ServingError,
    ShedError,
    ShutdownError,
)
from deeplearning4j_tpu.telemetry import context as context_mod
from deeplearning4j_tpu.telemetry import metrics as metrics_mod
from deeplearning4j_tpu.telemetry import trace as trace_mod
from deeplearning4j_tpu.util import envflags
from deeplearning4j_tpu.util.locks import TrackedLock

logger = logging.getLogger("deeplearning4j_tpu")

SERVING_GATE = "DL4J_TPU_SERVING"
SHED_POLICIES = ("reject_newest", "drop_oldest")

# serving latency spans sub-ms CPU smoke nets to multi-second cold paths
_LATENCY = metrics_mod.histogram(
    "dl4j_tpu_serving_latency_seconds",
    "End-to-end request latency (queue wait + dispatch), successes only",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5, 5.0, 10.0))
_P50 = metrics_mod.gauge(
    "dl4j_tpu_serving_latency_p50_seconds",
    "p50 request latency over the last 512 served requests")
_P99 = metrics_mod.gauge(
    "dl4j_tpu_serving_latency_p99_seconds",
    "p99 request latency over the last 512 served requests")
_QUEUE_DEPTH = metrics_mod.gauge(
    "dl4j_tpu_serving_queue_depth",
    "Requests currently queued (admitted, not yet dispatched)")
# observed request-size distribution (rows per submit, shed included) —
# the tuner's bucket re-cut signal (docs/TUNING.md); bucket bounds are
# the power-of-two skeleton BucketSpec defaults to
_REQUEST_ROWS = metrics_mod.histogram(
    "dl4j_tpu_request_rows",
    "Rows per submitted request (demand, before admission control)",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024))
_SHED = metrics_mod.counter(
    "dl4j_tpu_serving_shed_total",
    "Requests shed (refused or dropped) before dispatch, by reason",
    labelnames=("reason",))
_REQUESTS = metrics_mod.counter(
    "dl4j_tpu_serving_requests_total",
    "Admitted requests resolved, by outcome",
    labelnames=("outcome",))

# live servers for /healthz (weak: a dropped server must not pin itself)
_SERVERS: "weakref.WeakSet[InferenceServer]" = weakref.WeakSet()


class _Pending:
    """One admitted request: resolved exactly once with a result or a
    typed error; `event` is the caller's bounded-wait handle."""

    __slots__ = ("x", "n", "sig", "deadline", "event", "result", "error",
                 "enqueued_perf", "probe", "ctx", "tenant")

    def __init__(self, x: np.ndarray, deadline: Deadline):
        self.x = x
        self.n = x.shape[0]
        self.sig = buckets_mod.signature(x)
        self.deadline = deadline
        self.event = threading.Event()
        self.result: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None
        self.enqueued_perf = time.perf_counter()
        # True while this request HOLDS a half-open probe slot: a
        # dispatch result repays it via record_success/record_failure;
        # any no-dispatch resolution must release_probe() instead
        self.probe = False
        # the request's TraceContext (telemetry/context.py), minted at
        # admission while telemetry is on; None when untraced. The
        # dispatcher thread attaches it explicitly (contextvars don't
        # cross threads) so dispatch/resolve spans join the request trace
        self.ctx = None
        # resolved tenant name when the server runs under a
        # TenancyController (serving/tenancy.py); None otherwise
        self.tenant = None


def healthz_section() -> Optional[dict]:
    """Breaker + queue state over every LIVE server for /healthz; None
    when no server exists (training-only processes keep their historical
    /healthz payload byte-identical)."""
    servers = [s for s in list(_SERVERS) if not s.stopped]
    if not servers:
        return None
    snaps = [s.snapshot() for s in servers]
    return {
        "servers": snaps,
        "breaker_open": any(sn["breaker"]["state"] == OPEN for sn in snaps),
        "queue_depth": sum(sn["queue_depth"] for sn in snaps),
    }


class InferenceServer:
    """Continuous-batching inference with overload protection.

    Pass a `model` (anything with a jitted ``output(x)``; a mesh is
    built / used for data-axis sharding exactly like ParallelInference)
    or a raw ``dispatch(batch) -> outputs`` callable (tests, custom
    stacks). `buckets` defaults to power-of-two sizes aligned to the
    mesh's data axis, up to `batch_limit`.
    """

    def __init__(self, model=None, dispatch: Optional[Callable] = None,
                 mesh=None, batch_limit: int = 32, queue_limit: int = 64,
                 wait_ms: float = 2.0,
                 buckets: Optional[buckets_mod.BucketSpec] = None,
                 shed_policy: Optional[str] = None,
                 default_deadline_s: Optional[float] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 slow_fault_s: float = 0.25,
                 warmup_example=None,
                 tenancy=None,
                 name: str = "serving"):
        if model is None and dispatch is None:
            raise ValueError("InferenceServer needs a model or a dispatch "
                             "callable")
        self.name = name
        self.batch_limit = max(1, int(batch_limit))
        self.queue_limit = max(1, int(queue_limit))
        self.wait_ms = max(0.0, float(wait_ms))
        self.slow_fault_s = max(0.0, float(slow_fault_s))
        self.model = model
        self.mesh = mesh
        align = 1
        if dispatch is None:
            dispatch, align = self._build_model_dispatch(model, mesh)
        self._dispatch = dispatch
        self.buckets = buckets or buckets_mod.BucketSpec(
            self.batch_limit, align=align)
        if shed_policy is None:
            shed_policy = envflags.value("DL4J_TPU_SERVING_SHED",
                                         "reject_newest")
        if shed_policy not in SHED_POLICIES:
            raise ValueError(f"shed_policy {shed_policy!r} not in "
                             f"{SHED_POLICIES}")
        self.shed_policy = shed_policy
        if default_deadline_s is None:
            default_deadline_s = envflags.float_value(
                "DL4J_TPU_SERVING_DEADLINE", 0.0)
        # 0 / unset = no default deadline (Deadline(None) never expires)
        self._default_deadline_s = (float(default_deadline_s)
                                    if default_deadline_s
                                    and default_deadline_s > 0 else None)
        self.breaker = breaker or CircuitBreaker(
            failure_threshold=envflags.int_value(
                "DL4J_TPU_SERVING_BREAK_AFTER", 5),
            cooldown_s=envflags.float_value(
                "DL4J_TPU_SERVING_COOLDOWN", 1.0),
            probe_successes=envflags.int_value(
                "DL4J_TPU_SERVING_PROBES", 2))
        if self.breaker.on_open is None:
            self.breaker.on_open = self._on_breaker_open
        # the hottest lock in the tree (every admit, dispatch pop and
        # snapshot crosses it): TrackedLock is a raw threading.Lock
        # unless DL4J_TPU_LOCKCHECK turns the order sentinel on
        self._cond = threading.Condition(
            TrackedLock("serving.runtime.queue"))
        # a TenancyController swaps the FIFO for its deficit-round-robin
        # TenantQueue (same deque surface, weighted-fair pops); the plain
        # deque is bounded by queue_limit's shed policy at admission, not
        # by maxlen — a maxlen overflow would silently drop a request
        # whose caller is parked on its event
        self.tenancy = tenancy
        self._q = (tenancy.make_queue(self.queue_limit)
                   if tenancy is not None else
                   deque())  # guarded-by: self._cond  # jaxlint: disable=JX020 — bounded by the queue_limit shed policy at admission
        self._stopping = False  # guarded-by: self._cond
        self._stopped = False
        self._crash: Optional[BaseException] = None  # guarded-by: self._cond
        self._ema_latency_s: Optional[float] = None  # guarded-by: self._cond
        self._lat: "deque[float]" = deque(maxlen=512)  # guarded-by: self._cond
        self._depths: "deque[int]" = deque(maxlen=512)  # guarded-by: self._cond
        self.warmed_rows: set = set()
        self.dispatched_rows: set = set()
        # calls of the dispatch callable, failed ones included
        # (`snapshot()["dispatched_batches"]`)
        self._dispatched_batches = 0  # guarded-by: self._cond
        # raw reservoir behind dl4j_tpu_request_rows: the last 512
        # submitted row counts, the tuner's re-cut planning input
        self._row_sizes: "deque[int]" = deque(maxlen=512)
        self._warm_example = None  # first row template, for re-warms
        if warmup_example is not None:
            self.warmup(warmup_example)
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"InferenceServer-dispatch-{name}")
        self._thread.start()
        _SERVERS.add(self)

    # ------------------------------------------------------------------
    # dispatch construction / warmup
    # ------------------------------------------------------------------
    @staticmethod
    def _build_model_dispatch(model, mesh):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from deeplearning4j_tpu.parallel import mesh as mesh_mod

        if mesh is None:
            mesh = mesh_mod.build_mesh(
                mesh_mod.MeshSpec.data_parallel(len(jax.devices())))
        align = mesh.shape["data"]

        def dispatch(xp, _model=model, _mesh=mesh):
            sh = NamedSharding(_mesh, P("data", *([None] * (xp.ndim - 1))))
            xd = jax.device_put(xp, sh)
            # ambient mesh around the jitted forward: kernel call sites
            # run per batch shard (parallel/mesh.py)
            with jax.set_mesh(_mesh):
                return np.asarray(_model.output(xd))

        return dispatch, align

    def warmup(self, example) -> None:
        """Dispatch one batch per bucket size so every executable exists
        before traffic arrives: steady state then re-runs warmed shapes
        and the PR 4 retrace detector stays silent. `example` is a real
        request array (leading batch axis included); its first row is
        the template."""
        row = np.asarray(example)[:1]
        self._warm_example = row  # template for tuner re-cut re-warms
        sig = buckets_mod.signature(row)
        for b in self.buckets.sizes:
            xb = np.repeat(row, b, axis=0)
            self._dispatch(xb)
            self.warmed_rows.add((sig, b))

    def observed_rows(self) -> list:
        """The request-size reservoir (last 512 submits) — the bucket
        re-cut rule's planning input (tuning/rules.py plan_buckets)."""
        return list(self._row_sizes)

    def recut_buckets(self, sizes, example=None) -> buckets_mod.BucketSpec:
        """Swap in a re-cut BucketSpec, warming any NEW sizes first so
        the swap never cold-compiles in steady state: the dispatcher
        keeps draining under the old spec while each unseen size is
        dispatched once here, and only then does the spec pointer move
        (one atomic assignment under the queue lock). `align` and
        `max_batch` invariants carry over from the live spec; the old
        executables stay in jit cache, so an immediate revert (the SLO
        gate's) is also warm. docs/TUNING.md "Bucket re-cut"."""
        spec = buckets_mod.BucketSpec(self.batch_limit,
                                      align=self.buckets.align,
                                      sizes=sizes)
        row = example if example is not None else self._warm_example
        if row is not None:
            row = np.asarray(row)[:1]
            sig = buckets_mod.signature(row)
            for b in spec.sizes:
                if (sig, b) not in self.warmed_rows:
                    self._dispatch(np.repeat(row, b, axis=0))
                    self.warmed_rows.add((sig, b))
        with self._cond:
            self.buckets = spec
        return spec

    # ------------------------------------------------------------------
    # client API
    # ------------------------------------------------------------------
    def output(self, x, deadline_s: Optional[float] = None,
               tenant: Optional[str] = None) -> np.ndarray:
        """Blocking inference; raises a typed ServingError subclass when
        the request is shed, expired, over tenant quota, broken-circuit,
        or the runtime is down. Never blocks past the deadline (plus one
        wait slice)."""
        req = self.submit(x, deadline_s=deadline_s, tenant=tenant)
        return self.result(req)

    def submit(self, x, deadline_s: Optional[float] = None,
               tenant: Optional[str] = None) -> _Pending:
        """Admission control: refuse (typed) or enqueue. See module
        docstring for the decision order. While telemetry is on, every
        request is minted a TraceContext at admission; the admission
        decision itself is a span in that trace (shed/reject decisions
        carry a `rejected` reason), and an enqueued request emits a flow
        arrow that the batch dispatch span on the dispatcher thread
        binds to (docs/TELEMETRY.md "Correlated tracing")."""
        x = np.asarray(x)
        if x.ndim == 0:
            raise ValueError("request must have a leading batch axis")
        deadline = Deadline(deadline_s if deadline_s is not None
                            else self._default_deadline_s)
        req = _Pending(x, deadline)
        # demand distribution, observed BEFORE admission control: shed
        # requests are exactly the ones a better bucket cut might serve
        _REQUEST_ROWS.observe(req.n)
        self._row_sizes.append(int(req.n))
        if self.tenancy is not None:
            from deeplearning4j_tpu.serving.tenancy import DEFAULT_TENANT

            req.tenant = tenant or DEFAULT_TENANT
        tr = trace_mod.tracer()
        if not tr.enabled:
            return self._admit(req, tr)
        req.ctx = context_mod.new_trace()
        with context_mod.activate(req.ctx):
            return self._admit(req, tr)

    def _admit(self, req: _Pending, tr) -> _Pending:
        deadline = req.deadline
        with tr.span("serving.admission", category="serving") as adm:
            if self.tenancy is not None:
                # per-tenant quota runs IN FRONT of the shared queue (and
                # outside its lock): an over-quota tenant sheds itself
                # before it can touch anyone else's admission estimate
                try:
                    req.tenant = self.tenancy.admit(req.tenant, rows=req.n)
                except ServingError:
                    adm.set(rejected="tenant_quota")
                    self._shed("tenant_quota")
                    raise
            with self._cond:
                if self._crash is not None:
                    raise DispatcherCrashedError(
                        f"serving dispatcher died: {self._crash!r}",
                        cause=self._crash)
                if self._stopping:
                    raise ShutdownError("serving runtime is shut down")
                allowed, holds_probe = self.breaker.admit()
                if not allowed:
                    adm.set(rejected="breaker_open")
                    self._shed("breaker_open")
                    raise CircuitOpenError(
                        "circuit breaker open (consecutive dispatch "
                        "failures or non-finite outputs)",
                        retry_after_s=self.breaker.retry_after_s())
                req.probe = holds_probe
                if holds_probe:
                    # the half-open probe grant, visible in /trace as its
                    # own marker on the caller's lane
                    tr.add_instant("serving.breaker_probe",
                                   category="serving")
                est = self._admission_estimate_locked()
                if deadline.remaining() < est:
                    self._release_if_probe(req)
                    adm.set(rejected="deadline")
                    self._shed("deadline")
                    raise DeadlineExceededError(
                        f"deadline {deadline.seconds:.3g}s cannot be met: "
                        f"estimated time to result {est:.3g}s at queue "
                        f"depth {len(self._q)}")
                if len(self._q) >= self.queue_limit:
                    # the retry hint floors the queue estimate with the
                    # breaker's cooldown remaining: a shed raced against
                    # an opening circuit must not invite a retry that
                    # lands inside the open window and burns an attempt
                    hint = self._retry_hint_locked(est)
                    if self.shed_policy == "drop_oldest":
                        oldest = self._q.popleft()
                        self._release_if_probe(oldest)
                        self._shed("drop_oldest")
                        if self.tenancy is not None:
                            self.tenancy.note_shed(oldest.tenant,
                                                   "drop_oldest")
                        self._resolve(oldest, error=ShedError(
                            "dropped from a full queue to admit a newer "
                            "request (shed_policy=drop_oldest)",
                            retry_after_s=hint), outcome="shed")
                    else:
                        self._release_if_probe(req)
                        adm.set(rejected="queue_full")
                        self._shed("queue_full")
                        if self.tenancy is not None:
                            self.tenancy.note_shed(req.tenant, "queue_full")
                        raise ShedError(
                            f"queue full ({self.queue_limit} requests; "
                            f"shed_policy=reject_newest)",
                            retry_after_s=hint)
                self._q.append(req)
                depth = len(self._q)
                _QUEUE_DEPTH.set(depth)
                self._cond.notify()
            adm.set(rows=req.n, depth=depth)
        if req.ctx is not None:
            # flow start on the caller's lane: the dispatcher's batch
            # span emits the matching finish, drawing the request ->
            # batch arrow in Perfetto
            tr.add_flow("serving.batch", flow_id=req.ctx.trace_id,
                        phase="s", category="serving")
        return req

    def result(self, req: _Pending) -> np.ndarray:
        """Bounded wait for one submitted request (JX012 posture: every
        wait carries a timeout; liveness is re-checked per slice). The
        wait-and-unwrap is the request trace's `serving.resolve` span."""
        if req.ctx is None:
            return self._result_inner(req)
        with context_mod.activate(req.ctx):
            t0 = time.perf_counter()
            try:
                out = self._result_inner(req)
            except BaseException as e:
                trace_mod.tracer().add_span(
                    "serving.resolve", (time.perf_counter() - t0) * 1e3,
                    category="serving", outcome=type(e).__name__)
                raise
            trace_mod.tracer().add_span(
                "serving.resolve", (time.perf_counter() - t0) * 1e3,
                category="serving", outcome="ok")
            return out

    def _result_inner(self, req: _Pending) -> np.ndarray:
        while not req.event.wait(min(0.05, max(
                0.001, req.deadline.remaining()
                if req.deadline.seconds is not None else 0.05))):
            if req.deadline.expired:
                self._expire_queued(req)
                if not req.event.is_set():
                    # in flight (or just resolved): the caller's budget
                    # is spent either way
                    raise DeadlineExceededError(
                        f"request missed its {req.deadline.seconds:.3g}s "
                        f"deadline (in flight or queued behind a slow "
                        f"dispatch)")
            with self._cond:
                crash = self._crash
            if crash is not None and not req.event.is_set():
                raise DispatcherCrashedError(
                    f"serving dispatcher died: {crash!r}", cause=crash)
        if req.error is not None:
            raise req.error
        return req.result

    def shutdown(self, timeout: float = 5.0) -> None:
        """Drain: finish the in-flight batch, resolve every queued
        request with ShutdownError, stop the dispatcher. Idempotent;
        bounded by `timeout`."""
        with self._cond:
            self._stopping = True
            self._cond.notify_all()
        dl = Deadline(timeout)
        while self._thread.is_alive() and not dl.expired:
            self._thread.join(0.05)
        # belt: if the thread was already dead (crash path) anything
        # still queued is resolved here — a shutdown must leave zero
        # parked callers behind
        self._drain(ShutdownError("serving runtime shut down"),
                    outcome="shutdown", shed_reason="shutdown")
        self._stopped = True

    @property
    def stopped(self) -> bool:
        return self._stopped

    @property
    def crashed(self) -> bool:
        """True once the dispatcher thread has died on an unexpected
        error — the autoscaler's pull-driven replica health check."""
        with self._cond:
            return self._crash is not None

    def snapshot(self) -> dict:
        """Machine-readable state for /healthz and the bench row."""
        with self._cond:  # rings are written under this lock too
            depth = len(self._q)
            lat = sorted(self._lat)
            depths = sorted(self._depths)
            stopping = self._stopping
            ema = self._ema_latency_s
            batches = self._dispatched_batches
            by_tenant = (self._q.queued_by_tenant()
                         if self.tenancy is not None else None)

        def pct(vals, q):
            if not vals:
                return None
            return vals[min(len(vals) - 1, int(q * (len(vals) - 1)))]

        snap = {
            "name": self.name,
            "queue_depth": depth,
            "queue_limit": self.queue_limit,
            "queue_depth_p50": pct(depths, 0.5),
            "shed_policy": self.shed_policy,
            "buckets": list(self.buckets.sizes),
            "latency_p50_s": (round(pct(lat, 0.5), 6) if lat else None),
            "latency_p99_s": (round(pct(lat, 0.99), 6) if lat else None),
            "ema_latency_s": (round(ema, 6) if ema is not None else None),
            "dispatched_batches": batches,
            "breaker": self.breaker.snapshot(),
            "stopping": stopping,
        }
        if by_tenant is not None:
            snap["queued_by_tenant"] = by_tenant
        return snap

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------
    def _shed(self, reason: str) -> None:
        _SHED.labels(reason).inc()

    def _release_if_probe(self, req: _Pending) -> None:
        """Repay a half-open probe slot when its request resolves WITHOUT
        a dispatch result (queue expiry, drop_oldest victim, drain,
        crash): record_success/record_failure never run for it, and an
        unreturned slot wedges the breaker in HALF_OPEN rejecting every
        future request."""
        if req.probe:
            req.probe = False
            self.breaker.release_probe()

    def _retry_hint_locked(self, est: Optional[float] = None) -> float:
        """Retry-after hint for shed resolutions: the queue-pressure
        estimate, floored by the breaker's cooldown remaining when the
        circuit is open — `submit_with_retry` sleeps on this hint, and a
        hint shorter than the open window guarantees the next attempt
        dies on CircuitOpenError instead of being served."""
        if est is None:
            est = self._admission_estimate_locked()
        return max(est, self.breaker.retry_after_s())

    def _admission_estimate_locked(self) -> float:
        """Expected submit->result time at the current depth: the
        coalesce window plus the dispatch-latency EMA once per already-
        queued bucketful ahead of this request (cond lock held)."""
        est = self.wait_ms / 1000.0
        if self._ema_latency_s is not None:
            waves = 1 + len(self._q) // self.batch_limit
            est += self._ema_latency_s * waves
        return est

    def _resolve(self, req: _Pending, result=None, error=None,
                 outcome: str = "ok") -> None:
        req.result = result
        req.error = error
        _REQUESTS.labels(outcome).inc()
        if self.tenancy is not None and req.tenant is not None:
            self.tenancy.observe(req.tenant, outcome)
        req.event.set()

    def _expire_queued(self, req: _Pending) -> None:
        """Caller-side deadline expiry: remove + resolve if still
        queued (under the lock, so the dispatcher can't also take it)."""
        with self._cond:
            try:
                self._q.remove(req)
            except ValueError:
                return  # popped for dispatch (or already resolved)
            _QUEUE_DEPTH.set(len(self._q))
        self._release_if_probe(req)
        self._shed("deadline")
        self._resolve(req, error=DeadlineExceededError(
            f"deadline {req.deadline.seconds:.3g}s expired in queue"),
            outcome="deadline")

    def _pop_expired_locked(self) -> List[_Pending]:
        out = []
        while self._q and self._q[0].deadline.expired:
            out.append(self._q.popleft())
        if out:
            _QUEUE_DEPTH.set(len(self._q))
        return out

    def _fail_expired(self, expired: List[_Pending]) -> None:
        for r in expired:
            self._release_if_probe(r)
            self._shed("deadline")
            self._resolve(r, error=DeadlineExceededError(
                f"deadline {r.deadline.seconds:.3g}s expired in queue"),
                outcome="deadline")

    def _next_batch(self) -> Optional[List[_Pending]]:
        """Pop + coalesce: FIFO head defines the shape signature; only
        matching requests join, never past `batch_limit` rows (an
        oversize single request dispatches alone). Returns None when
        stopping and nothing is queued."""
        while True:
            with self._cond:
                expired = self._pop_expired_locked()
                if self._stopping:
                    # drain semantics: the in-flight batch completes,
                    # everything still queued resolves with
                    # ShutdownError (in _loop's drain) — shutdown time
                    # is bounded by ONE dispatch, not the queue depth
                    first = None
                elif self._q:
                    first = self._q.popleft()
                    _QUEUE_DEPTH.set(len(self._q))
                    self._depths.append(len(self._q))
                else:
                    self._cond.wait(0.05)
                    first = False  # retry
            if expired:
                self._fail_expired(expired)
            if first is None:
                return None
            if first is not False:
                break
        batch = [first]
        total = first.n
        end = time.perf_counter() + self.wait_ms / 1000.0
        while total < self.batch_limit:
            with self._cond:
                expired = self._pop_expired_locked()
                nxt = self._q[0] if self._q else None
                take = (nxt is not None and nxt.sig == first.sig
                        and total + nxt.n <= self.batch_limit)
                if take:
                    self._q.popleft()
                    _QUEUE_DEPTH.set(len(self._q))
                stop_now = self._stopping
                if not take and nxt is None and not stop_now:
                    rem = end - time.perf_counter()
                    if rem > 0:
                        self._cond.wait(min(rem, 0.02))
            if expired:
                self._fail_expired(expired)
            if take:
                batch.append(nxt)
                total += nxt.n
                continue
            if nxt is not None or stop_now:
                break  # signature/size boundary, or draining
            if time.perf_counter() >= end:
                break
        return batch

    def _fail_batch(self, batch: List[_Pending], error: ServingError,
                    outcome: str, reason: str) -> None:
        # record_failure repays the batch's probe slot (max_probes=1:
        # at most one per batch); clear the flags so no later path
        # double-releases
        for r in batch:
            r.probe = False
        self.breaker.record_failure(reason)
        for r in batch:
            self._resolve(r, error=error, outcome=outcome)

    def _trace_batch_members(self, batch: List[_Pending], dt_ms: float,
                             target: int, outcome: str) -> None:
        """Per-member dispatch spans + flow finishes on the dispatcher
        lane: each admitted request's trace gets its OWN `serving.dispatch`
        span (stamped with that request's ids, explicit cross-thread
        attach) and the flow arrow from its enqueue binds here — so a p99
        outlier's trace shows which batch carried it and who rode along."""
        tr = trace_mod.tracer()
        if not tr.enabled:
            return
        for r in batch:
            if r.ctx is None:
                continue
            with context_mod.activate(r.ctx):
                tr.add_flow("serving.batch", flow_id=r.ctx.trace_id,
                            phase="f", category="serving")
                tr.add_span("serving.dispatch", dt_ms, category="serving",
                            rows=r.n, bucket=target, outcome=outcome,
                            batch_size=len(batch))

    def _dispatch_batch(self, batch: List[_Pending]) -> None:
        total = sum(r.n for r in batch)
        target = self.buckets.padded_size(total)
        sig = batch[0].sig
        member_traces = [r.ctx.trace_id for r in batch
                         if r.ctx is not None]
        t0 = time.perf_counter()
        try:
            chaos.fault_point("serving_dispatch")
            if chaos.silent_fault("serving_slow"):
                time.sleep(self.slow_fault_s)
            x = (np.concatenate([r.x for r in batch], axis=0)
                 if len(batch) > 1 else batch[0].x)
            xp = buckets_mod.pad_rows(x, target)
            with trace_mod.tracer().span("serving.dispatch_batch",
                                         category="serving",
                                         rows=total, bucket=target) as sp:
                if member_traces:
                    sp.set(member_traces=member_traces)
                with self._cond:
                    self._dispatched_batches += 1
                out = np.asarray(self._dispatch(xp))
            self.dispatched_rows.add((sig, target))
            if chaos.silent_fault("serving_nan"):
                out = np.full_like(out.astype(np.float32), np.nan)
            from deeplearning4j_tpu.resilience.sentry import tree_all_finite

            if not tree_all_finite(out):
                raise NonFiniteOutputError(
                    f"non-finite outputs from bucket {target} "
                    f"(result discarded)")
        except NonFiniteOutputError as e:
            self._trace_batch_members(
                batch, (time.perf_counter() - t0) * 1e3, target,
                "nonfinite")
            self._fail_batch(batch, e, "nonfinite", "non-finite output")
        except Exception as e:
            self._trace_batch_members(
                batch, (time.perf_counter() - t0) * 1e3, target,
                "dispatch_error")
            self._fail_batch(
                batch, DispatchFailedError(
                    f"batch dispatch failed: {type(e).__name__}: {e}",
                    cause=e),
                "dispatch_error", f"{type(e).__name__}: {e}")
        else:
            now = time.perf_counter()
            dt = now - t0
            self._trace_batch_members(batch, dt * 1e3, target, "ok")
            # the EMA feeds _admission_estimate_locked on admit threads:
            # update it under the same lock those reads hold
            with self._cond:
                self._ema_latency_s = (
                    dt if self._ema_latency_s is None
                    else 0.8 * self._ema_latency_s + 0.2 * dt)
            for r in batch:  # record_success repays the batch's probe
                r.probe = False
            self.breaker.record_success()
            off = 0
            lats = []
            for r in batch:
                r.result = out[off:off + r.n]
                off += r.n
                lat = now - r.enqueued_perf
                _LATENCY.observe(lat)
                lats.append(lat)
                _REQUESTS.labels("ok").inc()
                if self.tenancy is not None and r.tenant is not None:
                    self.tenancy.observe(r.tenant, "ok", latency_s=lat)
                r.event.set()
            # the ring is read by snapshot() from other threads: append
            # under the lock or sorted()/list() there hits "deque
            # mutated during iteration"
            with self._cond:
                self._lat.extend(lats)
                lat_sorted = sorted(self._lat)
            _P50.set(lat_sorted[int(0.5 * (len(lat_sorted) - 1))])
            _P99.set(lat_sorted[int(0.99 * (len(lat_sorted) - 1))])

    def _drain(self, error: ServingError, outcome: str,
               shed_reason: Optional[str] = None) -> None:
        with self._cond:
            pending = list(self._q)
            self._q.clear()
            _QUEUE_DEPTH.set(0)
        for r in pending:
            self._release_if_probe(r)
            if shed_reason is not None:
                self._shed(shed_reason)
            self._resolve(r, error=error, outcome=outcome)

    def _on_breaker_open(self, reason: str) -> None:
        logger.warning("serving circuit breaker OPEN (%s); rejecting "
                       "requests for %.3gs", reason,
                       self.breaker.cooldown_s)
        from deeplearning4j_tpu.telemetry import flight as flight_mod

        flight_mod.dump("serving_breaker", note=reason)

    def _loop(self) -> None:
        inflight: List[_Pending] = []
        tr = trace_mod.tracer()
        if tr.enabled:
            # label the dispatcher's lane in the Chrome export — serving
            # spans otherwise land on an anonymous tid
            tr.set_thread_name(threading.get_ident(),
                               f"serving-dispatch-{self.name}")
        try:
            while True:
                # waiting for the first request and gathering the rest
                with trace_mod.tracer().span("serving.coalesce",
                                             category="serving"):
                    batch = self._next_batch()
                if batch is None:
                    break
                inflight = batch
                self._dispatch_batch(batch)
                inflight = []
        except BaseException as e:  # a dispatcher bug must not strand callers
            with self._cond:
                self._crash = e
            logger.exception("serving dispatcher crashed")
            err = DispatcherCrashedError(
                f"serving dispatcher died: {e!r}", cause=e)
            # the crashing batch was already popped — the queue drain
            # alone would strand exactly those callers (and their probe
            # slots: the crash skipped record_success/record_failure)
            for r in inflight:
                if not r.event.is_set():
                    self._release_if_probe(r)
                    self._resolve(r, error=err, outcome="crashed")
            self._drain(err, outcome="crashed")
        else:
            self._drain(ShutdownError("serving runtime shut down"),
                        outcome="shutdown", shed_reason="shutdown")
