"""Tracer — spans over a bounded ring buffer, exported as Chrome trace JSON.

Span timestamps are NTP-immune: a wall/perf anchor pair is captured once
per Tracer and every span start is ``wall_anchor + (perf_counter() -
perf_anchor)`` — wall-aligned for readability, monotonic for correctness
(the same policy distributed/stats.py applies to EventStats, and the one
jaxlint JX007 enforces repo-wide: durations never come from ``time.time()``
subtraction).

Export targets the Chrome trace-event format ("X" complete events with
microsecond ts/dur), which loads directly in Perfetto or chrome://tracing.
``merge_training_stats`` ingests distributed ``TrainingStats`` (live
objects or their ``to_json()`` dicts) so Spark-style orchestration-phase
timelines land in the same trace, one lane per worker.

One seam, three sinks. ``tracer().span(name, category, **attrs)`` (and
``step_span`` for the optimizer step) is the only call a site makes; on
enter/exit the process tracer feeds

  the profiler  a ``jax.profiler.TraceAnnotation("dl4j." + name)`` entered
                around the work (``StepTraceAnnotation`` for the step): a
                no-op unless a profiler session records host events, and
                then on the device trace's own clock;
  the ring      a ``SpanRecord``, only while ``DL4J_TPU_TELEMETRY``
                (util/envflags.py) is on; names stay unprefixed;
  the account   always on: per span name calls, total seconds, max seconds
                and the summed ``bytes=`` attribute (``PhaseAccount``) —
                fixed memory, no ``SpanRecord``. ``fit_log()`` holds what
                each of the last fits added to it.

A ``Tracer`` built directly is a ring alone: disabled, its ``span()``
returns a shared no-op singleton — zero span records allocated, the
contract the disabled-mode tier-1 test asserts.
"""
from __future__ import annotations

import functools
import json
import os
import re
import statistics
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from deeplearning4j_tpu.telemetry import context as context_mod
from deeplearning4j_tpu.util import envflags

TELEMETRY_GATE = "DL4J_TPU_TELEMETRY"
BUFFER_GATE = "DL4J_TPU_TELEMETRY_BUFFER"
DEFAULT_CAPACITY = 65536

# tid base for merged distributed-stats lanes (real thread ids are process
# addresses, far above this; worker lanes must not collide with them in the
# viewer, so they get their own small-id block + thread_name metadata)
_WORKER_TID_BASE = 1000
_MASTER_TID = 999


class SpanRecord:
    """One completed span. `start` is anchored-wall seconds (see module
    docstring); `duration_ms` comes from perf_counter differences only.
    `phase` "X" is a complete span; "i" is a Chrome instant event (a
    point-in-time marker — retrace warnings etc. — with no duration);
    "s"/"f" are flow start/finish arrows (`flow_id` binds the pair —
    serving uses them to link each member request to the shared batch
    dispatch span). `trace_id`/`span_id`/`parent_id` are the correlation
    ids stamped from the active telemetry.context.TraceContext, None when
    the span was recorded outside any trace."""

    __slots__ = ("name", "category", "start", "duration_ms", "thread_id",
                 "attrs", "phase", "trace_id", "span_id", "parent_id",
                 "flow_id")

    def __init__(self, name: str, category: str, start: float,
                 duration_ms: float, thread_id: int,
                 attrs: Optional[Dict[str, Any]], phase: str = "X",
                 trace_id: Optional[str] = None,
                 span_id: Optional[str] = None,
                 parent_id: Optional[str] = None,
                 flow_id: Optional[str] = None):
        self.name = name
        self.category = category
        self.start = start
        self.duration_ms = duration_ms
        self.thread_id = thread_id
        self.attrs = attrs
        self.phase = phase
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.flow_id = flow_id

    def to_chrome(self) -> Dict[str, Any]:
        ev = {
            "name": self.name,
            "cat": self.category or "default",
            "ph": self.phase,
            "ts": round(self.start * 1e6, 3),
            "pid": os.getpid(),
            "tid": self.thread_id,
        }
        if self.phase == "X":
            ev["dur"] = round(self.duration_ms * 1e3, 3)
        elif self.phase in ("s", "f"):
            # flow arrows bind by id; "e"-binding attaches the finish to
            # the enclosing slice (the batch dispatch span)
            ev["id"] = self.flow_id
            if self.phase == "f":
                ev["bp"] = "e"
        else:  # instant events render process-wide in Perfetto
            ev["s"] = "p"
        args = dict(self.attrs) if self.attrs else {}
        if self.trace_id is not None:
            args["trace_id"] = self.trace_id
            if self.span_id is not None:
                args["span_id"] = self.span_id
            if self.parent_id is not None:
                args["parent_id"] = self.parent_id
        if args:
            ev["args"] = args
        return ev


class _NullSpan:
    """Shared do-nothing span: the disabled-mode fast path. One module
    singleton serves every ``span()`` call, so a disabled tracer allocates
    nothing per call."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self

    def discard(self):
        return self

    def exclude(self, seconds):
        return self


NULL_SPAN = _NullSpan()
_EXHAUSTED = object()


class PhaseAccount:
    """Always-on totals per span name: ``[calls, total_s, max_s, bytes]``
    since the process started, plus the longest single span since the last
    ``mark()`` (a fit marks at its start, so its entry in ``fit_log()``
    carries its own maximum). One small list per distinct name — fixed
    memory however long the process runs. The lock is held for the four
    additions only: spans of one name close on many threads at once in
    serving."""

    __slots__ = ("_lock", "_by")

    def __init__(self):
        self._lock = threading.Lock()
        self._by: Dict[str, List[float]] = {}  # guarded-by: self._lock

    def add(self, name: str, seconds: float, nbytes: float = 0) -> None:
        with self._lock:
            e = self._by.get(name)
            if e is None:
                e = self._by[name] = [0, 0.0, 0.0, 0, 0.0]
            e[0] += 1
            e[1] += seconds
            if seconds > e[2]:
                e[2] = seconds
            e[3] += nbytes
            if seconds > e[4]:
                e[4] = seconds

    def mark(self) -> Dict[str, tuple]:
        """Snapshot for ``since``; restarts the maximum-since-mark."""
        with self._lock:
            for e in self._by.values():
                e[4] = 0.0
            return {k: tuple(e) for k, e in self._by.items()}

    def since(self, mark: Dict[str, tuple]) -> Dict[str, Dict[str, float]]:
        """What was added after ``mark``: {name: {calls, total_s, max_s,
        bytes}}, names with no new call left out."""
        out = {}
        with self._lock:
            now = {k: tuple(e) for k, e in self._by.items()}
        for name, e in sorted(now.items()):
            b = mark.get(name, (0, 0.0, 0.0, 0, 0.0))
            if e[0] > b[0]:
                out[name] = {"calls": e[0] - b[0], "total_s": e[1] - b[1],
                             "max_s": e[4], "bytes": e[3] - b[3]}
        return out

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """{name: {calls, total_s, max_s, bytes}} since process start."""
        with self._lock:
            return {k: {"calls": e[0], "total_s": e[1], "max_s": e[2],
                        "bytes": e[3]}
                    for k, e in sorted(self._by.items())}


def _attr_bytes(attrs: Optional[Dict[str, Any]]):
    """The numeric `bytes=` attribute of a span, else 0."""
    b = attrs.get("bytes") if attrs else None
    return b if isinstance(b, (int, float)) else 0


_annotations = None


def _profiler_annotations():
    """(TraceAnnotation, StepTraceAnnotation), imported at the first span:
    this module is imported by tools that never touch jax."""
    global _annotations
    if _annotations is None:
        from jax import profiler

        _annotations = (profiler.TraceAnnotation,
                        profiler.StepTraceAnnotation)
    return _annotations


class _Span:
    """One open span of a tracer with any sink on. The ring flag is read
    when the span is made, so a gate flipped mid-span cannot half-record
    it."""

    __slots__ = ("_tracer", "name", "category", "attrs", "_t0", "_ctx",
                 "_token", "_ring", "_annotation", "_discarded",
                 "_excluded")

    def __init__(self, tracer: "Tracer", name: str, category: str,
                 attrs: Optional[Dict[str, Any]],
                 step_num: Optional[int] = None):
        self._tracer = tracer
        self.name = name
        self.category = category
        self.attrs = attrs
        self._ring = tracer.enabled
        self._discarded = False
        self._excluded = 0.0
        self._ctx = None
        self._token = None
        self._annotation = None
        if tracer.account is not None:
            plain, step = _profiler_annotations()
            self._annotation = (
                plain("dl4j." + name) if step_num is None
                else step("dl4j." + name, step_num=step_num))

    def set(self, **attrs):
        """Attach attributes mid-span (rendered as Chrome `args`; a
        numeric `bytes` is summed by the phase account)."""
        if self.attrs is None:
            self.attrs = {}
        self.attrs.update(attrs)
        return self

    def discard(self):
        """Feed neither the ring nor the account at exit (the wait that
        only learned the iterator had ended)."""
        self._discarded = True
        return self

    def exclude(self, seconds: float):
        """Take `seconds` this span was open on something else's behalf
        off the duration the ring and the account get (step k's span is
        open over the etl and put of batch k+1, which have spans of
        their own). The profiler's annotation covers the whole interval."""
        self._excluded += seconds
        return self

    def __enter__(self):
        if self._ring:
            # inherit the active trace context: this span becomes a child
            # of the current span AND the parent of anything nested in it
            cur = context_mod.current()
            if cur is not None:
                self._ctx = cur.child()
                self._token = context_mod.attach(self._ctx)
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        if self._token is not None:
            context_mod.detach(self._token)
        if self._discarded:
            return False
        tracer = self._tracer
        duration = max(0.0, t1 - self._t0 - self._excluded)
        if tracer.account is not None:
            tracer.account.add(self.name, duration, _attr_bytes(self.attrs))
        if self._ring:
            tracer._record(self.name, self.category, self._t0, duration,
                           self.attrs, ctx=self._ctx)
        return False


class Tracer:
    """Thread-safe span collector with a bounded ring buffer.

        tr = Tracer(enabled=True)
        with tr.span("step", category="train"):
            ...
        tr.export_chrome("trace.json")   # open in Perfetto

    The buffer is a deque(maxlen=capacity): the newest `capacity` spans
    survive, `dropped` counts the overwritten ones. Export is lossless
    over everything the buffer holds.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 enabled: bool = False,
                 account: Optional[PhaseAccount] = None):
        # with an account (the process tracer's) spans also feed it and
        # the profiler (module docstring); without, a ring alone
        self.account = account
        self._lock = threading.Lock()
        self._buf: deque = deque(maxlen=max(1, int(capacity)))  # guarded-by: self._lock
        self._total = 0  # guarded-by: self._lock
        self.enabled = bool(enabled)
        self._thread_names: Dict[int, str] = {}
        # anchor pair: wall-aligned, perf-advanced (NTP-immune starts)
        self._wall0 = time.time()
        self._perf0 = time.perf_counter()

    # ------------------------------------------------------------------
    # recording
    # ------------------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self._buf.maxlen or 0  # noqa: DLC002 — maxlen is fixed at construction; a lock-free read can never be torn or stale

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._total - len(self._buf)

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)

    def _wall_at(self, perf_t: float) -> float:
        return self._wall0 + (perf_t - self._perf0)

    def span(self, name: str, category: str = "", **attrs):
        """Context-manager span into every sink that is on; the no-op
        singleton when none is."""
        return self._open(name, category, attrs, None)

    def step_span(self, name: str, step_num: int, category: str = "",
                  **attrs):
        """`span` for one optimizer step: the profiler gets a
        ``StepTraceAnnotation`` carrying `step_num`, so its tools group
        the device work by step."""
        return self._open(name, category, attrs, int(step_num))

    def _open(self, name, category, attrs, step_num):
        if not self.enabled and self.account is None:
            return NULL_SPAN
        return _Span(self, name, category, attrs or None, step_num)

    def spanned(self, name: str, iterable, category: str = ""):
        """Iterate `iterable` with every `next()` inside a span of its
        own, open WHILE the iterator works (the fit loop's `etl`, the
        prefetch thread's `produce`). The call that only finds the
        iterator exhausted is left out of the ring and the account."""
        source = iter(iterable)
        while True:
            with self.span(name, category) as sp:
                item = next(source, _EXHAUSTED)
                if item is _EXHAUSTED:
                    sp.discard()
                    return
            yield item

    def _record(self, name: str, category: str, perf_start: float,
                duration_s: float, attrs: Optional[Dict[str, Any]],
                ctx=None) -> None:
        if ctx is None:
            ctx = context_mod.current()
        rec = SpanRecord(name, category, self._wall_at(perf_start),
                         duration_s * 1e3, threading.get_ident(), attrs)
        if ctx is not None:
            rec.trace_id = ctx.trace_id
            rec.span_id = ctx.span_id
            rec.parent_id = ctx.parent_id
        with self._lock:
            self._buf.append(rec)
            self._total += 1

    def add_span(self, name: str, duration_ms: float, category: str = "",
                 thread_id: Optional[int] = None,
                 start: Optional[float] = None, **attrs) -> None:
        """Record an already-measured span (e.g. the ETL wait the fit loops
        time themselves). `start` is anchored-wall seconds; default = the
        span ended now and started `duration_ms` ago. The active
        TraceContext's ids are stamped on (the span reads as a child of
        the current span). Feeds the phase account and, while the gate is
        on, the ring; an interval that is already over cannot reach the
        profiler."""
        if self.account is not None:
            self.account.add(name, duration_ms / 1e3, _attr_bytes(attrs))
        if not self.enabled:
            return
        if start is None:
            start = self._wall_at(time.perf_counter()) - duration_ms / 1e3
        rec = SpanRecord(name, category, start, float(duration_ms),
                         threading.get_ident() if thread_id is None
                         else int(thread_id), attrs or None)
        ctx = context_mod.current()
        if ctx is not None:
            rec.trace_id = ctx.trace_id
            rec.span_id = context_mod.new_span_id()
            rec.parent_id = ctx.span_id
        with self._lock:
            self._buf.append(rec)
            self._total += 1

    def add_instant(self, name: str, category: str = "",
                    thread_id: Optional[int] = None, **attrs) -> None:
        """Record a point-in-time marker (Chrome "i" event) — e.g. the
        retrace detector's warning flags. No-op when disabled."""
        if not self.enabled:
            return
        rec = SpanRecord(name, category,
                         self._wall_at(time.perf_counter()), 0.0,
                         threading.get_ident() if thread_id is None
                         else int(thread_id), attrs or None, phase="i")
        ctx = context_mod.current()
        if ctx is not None:
            rec.trace_id = ctx.trace_id
            rec.span_id = context_mod.new_span_id()
            rec.parent_id = ctx.span_id
        with self._lock:
            self._buf.append(rec)
            self._total += 1

    def add_flow(self, name: str, flow_id: str, phase: str,
                 category: str = "", thread_id: Optional[int] = None,
                 **attrs) -> None:
        """Record one end of a Chrome flow arrow. `phase` is "s" (start,
        at the producer — e.g. a serving request at enqueue) or "f"
        (finish, at the consumer — inside the batch dispatch span);
        `flow_id` binds the pair. No-op when disabled."""
        if phase not in ("s", "f"):
            raise ValueError(f"flow phase must be 's' or 'f', got {phase!r}")
        if not self.enabled:
            return
        rec = SpanRecord(name, category,
                         self._wall_at(time.perf_counter()), 0.0,
                         threading.get_ident() if thread_id is None
                         else int(thread_id), attrs or None, phase=phase,
                         flow_id=str(flow_id))
        ctx = context_mod.current()
        if ctx is not None:
            rec.trace_id = ctx.trace_id
            rec.span_id = context_mod.new_span_id()
            rec.parent_id = ctx.span_id
        with self._lock:
            self._buf.append(rec)
            self._total += 1

    def set_thread_name(self, thread_id: int, name: str) -> None:
        """Label a lane in the exported trace (Chrome thread_name
        metadata) — used by ParallelWrapper to give each device its own
        lane and by the layer profiler for its dedicated lane."""
        with self._lock:
            self._thread_names[int(thread_id)] = str(name)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._total = 0
            self._thread_names.clear()

    def records(self) -> List[SpanRecord]:
        with self._lock:
            return list(self._buf)

    def cursor(self) -> int:
        """Opaque position AFTER the newest record: feed it back to
        ``records_since`` to receive only what was recorded later."""
        with self._lock:
            return self._total

    def records_since(self, cursor: int):
        """Incremental ring read: records appended after ``cursor`` (a
        value previously returned by this method or ``cursor()``),
        the new cursor, and ``gap`` — how many records between the
        cursor and the oldest survivor were overwritten before this
        read (the ring outran the reader). ``cursor=0`` reads the whole
        surviving ring; a cursor from the future clamps to now. The
        delta seam behind telemetry frames (telemetry/export.py) and
        the ``/trace?cursor=`` incremental endpoint (ui/server.py).

        Returns ``(records, new_cursor, gap)``."""
        with self._lock:
            total = self._total
            oldest = total - len(self._buf)  # records ever evicted
            cur = max(int(cursor), 0)
            start = min(max(cur, oldest), total)
            gap = start - min(cur, start)
            recs = list(self._buf)
            if start > oldest:
                recs = recs[start - oldest:]
            return recs, total, gap

    def thread_names(self) -> Dict[int, str]:
        """Copy of the lane-label map (frames carry it so a merged
        fleet trace keeps per-thread lane names)."""
        with self._lock:
            return dict(self._thread_names)

    # ------------------------------------------------------------------
    # distributed-stats merge
    # ------------------------------------------------------------------
    def merge_training_stats(self, stats) -> int:
        """Ingest distributed/stats.py phase timings: a live TrainingStats,
        a list of EventStats, or the ``to_json()`` dict / its "events"
        list. Master events land on one lane, each worker on its own, with
        thread_name metadata so Perfetto labels the lanes. Returns the
        number of spans merged. Merging works even on a disabled tracer —
        it converts recorded history, it doesn't instrument a hot loop."""
        events = getattr(stats, "events", stats)
        if isinstance(events, dict):
            events = events.get("events", [])
        n = 0
        with self._lock:
            for e in events:
                if isinstance(e, dict):
                    key, start = e.get("key"), e.get("start_time")
                    dur, worker = e.get("duration_ms"), e.get("worker")
                    meta = e.get("meta") or None
                else:
                    key, start = e.key, e.start_time
                    dur, worker = e.duration_ms, e.worker
                    meta = e.meta or None
                if key is None or start is None or dur is None:
                    continue
                tid = (_MASTER_TID if worker is None
                       else _WORKER_TID_BASE + int(worker))
                self._thread_names.setdefault(
                    tid, "master" if worker is None else f"worker {worker}")
                # correlation ids ride EventStats.meta (distributed/stats.py)
                # and get promoted to first-class record fields so the
                # merged cross-worker trace joins on trace_id like any
                # locally recorded span
                trace_id = span_id = parent_id = None
                if meta and ("trace_id" in meta or "span_id" in meta
                             or "parent_id" in meta):
                    meta = dict(meta)
                    trace_id = meta.pop("trace_id", None)
                    span_id = meta.pop("span_id", None)
                    parent_id = meta.pop("parent_id", None)
                    meta = meta or None
                self._buf.append(SpanRecord(
                    str(key), "distributed", float(start), float(dur),
                    tid, meta, trace_id=trace_id, span_id=span_id,
                    parent_id=parent_id))
                self._total += 1
                n += 1
        return n

    # ------------------------------------------------------------------
    # export
    # ------------------------------------------------------------------
    def to_chrome_trace(self) -> Dict[str, Any]:
        """Chrome trace-event JSON object (loads in Perfetto as-is)."""
        with self._lock:
            records = list(self._buf)
            names = dict(self._thread_names)
        events: List[Dict[str, Any]] = [
            {"name": "thread_name", "ph": "M", "pid": os.getpid(),
             "tid": tid, "args": {"name": label}}
            for tid, label in sorted(names.items())
        ]
        events.extend(r.to_chrome() for r in records)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-span-name stats: count, total/mean/p50/max milliseconds."""
        by_name: Dict[str, List[float]] = {}
        for r in self.records():
            if r.phase != "X":  # instant markers carry no duration
                continue
            by_name.setdefault(r.name, []).append(r.duration_ms)
        out = {}
        for name in sorted(by_name):
            ds = by_name[name]
            out[name] = {
                "count": len(ds),
                "total_ms": round(sum(ds), 3),
                "mean_ms": round(sum(ds) / len(ds), 3),
                "p50_ms": round(statistics.median(ds), 3),
                "max_ms": round(max(ds), 3),
            }
        return out


# ---------------------------------------------------------------------------
# process-global tracer + gate plumbing
# ---------------------------------------------------------------------------

_global: Optional[Tracer] = None
_forced: Optional[bool] = None
_lock = threading.Lock()


def tracer() -> Tracer:
    """The process-global Tracer. Enablement re-reads the
    DL4J_TPU_TELEMETRY gate on every call (one env lookup) unless
    ``configure(enabled=...)`` forced it, so tests and long-lived
    processes can flip telemetry without restarting."""
    global _global
    t = _global
    if t is None:
        with _lock:
            t = _global
            if t is None:
                t = _global = Tracer(
                    capacity=envflags.int_value(BUFFER_GATE,
                                                DEFAULT_CAPACITY),
                    account=PhaseAccount())
    t.enabled = (envflags.enabled(TELEMETRY_GATE, False)
                 if _forced is None else _forced)
    return t


_KEEP = object()  # configure() sentinel: "enabled not passed" != None


def configure(enabled=_KEEP, capacity: Optional[int] = None) -> Tracer:
    """Programmatic override of the env gate: True/False forces, None
    returns control to DL4J_TPU_TELEMETRY, omitted leaves the current
    override untouched (so a capacity-only resize cannot silently flip
    tracing off). `capacity` rebuilds the global buffer, keeping the
    newest records up to the new bound."""
    global _global, _forced
    if enabled is not _KEEP:
        _forced = enabled
    with _lock:
        if capacity is not None:
            old = _global.records() if _global is not None else []
            _global = Tracer(
                capacity=capacity,
                account=(_global.account if _global is not None
                         else PhaseAccount()))
            for r in old[-capacity:]:
                _global._buf.append(r)
                _global._total += 1
    return tracer()


# ---------------------------------------------------------------------------
# the per-fit phase log
# ---------------------------------------------------------------------------

FIT_LOG_LENGTH = 64
_fits: deque = deque(maxlen=FIT_LOG_LENGTH)  # guarded-by: _lock
#: `perf_counter` at the first line of the package's `__init__`: the
#: origin of `t_start_s`
_import_t0 = time.perf_counter()


def record_import(t0: float, t1: float) -> None:
    """The package's `__init__`, at its last line: its import ran from
    `t0` to `t1` (`perf_counter`). Booked once as the `import` row of the
    phase account — no context manager goes around an import."""
    global _import_t0
    _import_t0 = t0
    tracer().account.add("import", t1 - t0)


def since_import(t: float) -> float:
    """`perf_counter` reading `t` as seconds since the package's import
    began (`fit_log()`'s `t_start_s`)."""
    return t - _import_t0


def record_fit(entry: Dict[str, Any]) -> None:
    """Append one finished fit (training/engine.py `TrainingRun.execute`)."""
    with _lock:
        _fits.append(entry)


def fit_log() -> List[Dict[str, Any]]:
    """The last fits of this process, oldest first, gate on or off:
    ``{path, steps, staged_ahead, t_start_s, wall_s, compiles, compile,
    phases: {name: {calls, total_s, max_s, bytes}}}`` — which entry point
    ran, how many optimizer steps, how many of them had their inputs
    handed to the runtime before the previous step's score was read (the
    fit loop's one-batch look-ahead: `steps - 1` when every batch could be
    staged), when the fit began (seconds since the package's import
    began) and its wall seconds, the XLA compilations inside it, what JAX
    traced, lowered and compiled inside it by stage and by function
    (`introspect.CompileAccount`: all zeros and an empty `by_fn` for a
    warm fit), and what each span name added to the phase account
    meanwhile (`max_s` the longest single span of the fit). Both accounts
    are the process's: spans closed and functions compiled by other
    threads during the fit (a server, a second fit) are counted in.
    docs/TELEMETRY.md "Reading a fit's phases"."""
    with _lock:
        return list(_fits)


# ---------------------------------------------------------------------------
# device scopes
# ---------------------------------------------------------------------------

SCOPE_PREFIX = "dl4j."

#: the words a layer may name a part of itself with (docs/TELEMETRY.md
#: "Device scopes" says what each covers)
SCOPE_PARTS = frozenset({
    # a block around its sub-layers
    "norm", "mlp",
    # recurrent mixers
    "proj", "retile", "conv", "gates", "rule", "solve", "scan", "norm_gate",
    "counters",
    # attention
    "rope", "attend", "out",
    # routed experts
    "route", "sort", "gather", "product", "combine", "shared",
    # their exchange between expert-parallel ranks: `exchange` holds `out`
    # (tokens to the ranks that hold their experts) and `back`
    "bucket", "exchange", "back",
    # the exit gate and distribution of a looped stack's loss
    "exit",
    # a row block's gradient, made in the forward visit of the block
    # (`losses.sparse_xent_weighted`)
    "grad",
})


def device_scope(part: Optional[str] = None, *, kind: Optional[str] = None,
                 layer=None):
    """The seam beside ``tracer().span()`` for the DEVICE's timeline: a
    ``jax.named_scope`` that puts one name on JAX's name stack while a
    step is traced. The stack is every operation's HLO ``op_name``, which
    the profiler's device trace carries as ``tf_op`` — metadata alone, so
    there is no gate: the compiled program is the same with or without.

        device_scope(kind="kimideltaattention", layer=3)   dl4j.L3.kimideltaattention
        device_scope(kind="loss")                          dl4j.loss
        device_scope("proj")                               proj

    A KIND takes the prefix: with ``layer`` (the index of the layer in
    its network, or a graph vertex's name) it is the scope a MODEL opens
    around the one call that applies that layer; without, a model's
    ``loss`` / ``update`` or the scope a block opens around a layer nested
    in it. A PART is a plain word of ``SCOPE_PARTS`` that a LAYER opens
    around a piece of its own work, nested under its kind; parts nest
    (``rule`` holds ``solve`` and ``scan``). Characters a path cannot hold
    become ``_``. Grammar and readers: docs/TELEMETRY.md "Device scopes"."""
    import jax

    if (part is None) == (kind is None):
        raise ValueError("device_scope takes a part or a kind")
    if kind is None:
        if layer is not None or part not in SCOPE_PARTS:
            raise ValueError(f"device scope part {part!r}: one of "
                             f"{sorted(SCOPE_PARTS)}, and no layer")
        return jax.named_scope(part)
    name = re.sub(r"[^a-z0-9_]", "_", kind.lower())
    if layer is not None:
        name = "L" + re.sub(r"[^A-Za-z0-9_-]", "_", str(layer)) + "." + name
    return jax.named_scope(SCOPE_PREFIX + name)


def traced(name: Optional[str] = None, category: str = ""):
    """Decorator span over a whole function call:

        @traced("checkpoint.write", category="checkpoint")
        def save(...): ...
    """

    def deco(fn):
        span_name = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with tracer().span(span_name, category=category):
                return fn(*args, **kwargs)

        return wrapper

    return deco
