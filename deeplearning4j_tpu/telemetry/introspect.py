"""Runtime introspection — compile watcher, HBM watermarks, layer spans.

PR 3's telemetry records *when* things happen; this module watches the
layer that determines TPU performance: XLA compilation, device memory,
and the per-layer cost structure of a step. Three instruments, all gated
by ``DL4J_TPU_TELEMETRY`` (the span gate — introspection IS spans+gauges):

  CompileWatcher   counts compilations and compile seconds two ways:
                   (a) the ``jax.monitoring`` listeners behind its
                   ``CompileAccount`` (they fire for EVERY trace, lowering
                   and backend compile in the process, raw ``jax.jit``
                   uses included, and count with the gate ON OR OFF:
                   seconds by stage and by function name, and the
                   persistent cache's hits, misses and read seconds —
                   ``fit_log()``'s ``compile`` and ``setup_log()``), and
                   (b) the ``util.jaxcompat.jit`` seam, which
                   fingerprints each call's ``(fn, abstract shapes/
                   dtypes)`` — a fingerprint never seen before is a
                   trace-cache miss, so the watcher times it as a
                   compile and feeds the RETRACE DETECTOR: one function
                   accumulating fingerprints past
                   ``DL4J_TPU_RETRACE_THRESHOLD`` (default 3) emits a
                   ``dl4j_tpu_retrace_warnings_total{fn}`` metric and a
                   Chrome-trace instant event ("why is every step
                   recompiling" answered by the trace itself).
  HBM watermarks   ``sample_hbm()`` reads ``device.memory_stats()`` at
                   span boundaries into per-device
                   ``dl4j_tpu_hbm_bytes{device}`` gauges and tracks a
                   per-fit peak; on backends without memory stats (CPU)
                   every call is a guarded no-op. ``fit_introspection``
                   closes the loop with PR 1's static analyzer: the peak
                   is compared against the DLA008/DLA009 predicted
                   working set (predicted-vs-actual published as gauges).
  layer spans      ``maybe_layer_spans`` — every Nth iteration
                   (``DL4J_TPU_PROFILE_LAYERS``, off by default) an
                   eager, per-layer forward/backward timing pass renders
                   one Chrome-trace lane per profile ("layer profile"),
                   the top-k layer table the ``profile`` CLI prints.

A fourth instrument, the COLLECTIVE CENSUS (``DL4J_TPU_COLLECTIVE_CENSUS``
on top of the telemetry gate, or ``configure_census(True)``): on every
trace-cache miss the watcher lowers and compiles the call FIRST
(donated buffers are consumed by the call itself, so the census must
run before it) and greps the optimized HLO module text for collective
ops — all-gather / all-reduce / reduce-scatter / collective-permute /
all-to-all — recording op count and per-device result-shape bytes per
watch name. This is the runtime twin of shardlint
(analysis/sharding.py): ``dryrun_multichip`` compares the static plan
against this census per collective class inside a +/-25% band. The
double compile is why the gate defaults off.

Disabled-path contract (the PR 3 policy, tier-1 asserted): with the gate
off every hook here is one attribute/env check — no span records, no
fingerprint sets, no metric children allocated. The compile account is
the exception by design: its listeners run only while JAX traces, lowers
or compiles, never in a warm step.
"""
from __future__ import annotations

import contextlib
import re
import threading
import time
import warnings
from typing import Any, Dict, List, Optional, Tuple

from deeplearning4j_tpu.telemetry import metrics as metrics_mod
from deeplearning4j_tpu.telemetry import trace as trace_mod
from deeplearning4j_tpu.util import envflags

RETRACE_GATE = "DL4J_TPU_RETRACE_THRESHOLD"
LAYER_GATE = "DL4J_TPU_PROFILE_LAYERS"
CENSUS_GATE = "DL4J_TPU_COLLECTIVE_CENSUS"

# dedicated trace lane (below the merge lanes at 999+; real thread ids
# are process addresses far above that block)
_LAYER_TID = 998

_compiles_total = metrics_mod.counter(
    "dl4j_tpu_compiles_total",
    "jit trace-cache misses observed at the jaxcompat.jit seam",
    labelnames=("fn",))
_compile_seconds = metrics_mod.counter(
    "dl4j_tpu_compile_seconds_total",
    "seconds spent in XLA backend compilation (jax.monitoring)")
_backend_compiles = metrics_mod.counter(
    "dl4j_tpu_backend_compiles_total",
    "XLA backend compilations observed process-wide (jax.monitoring)")
_retrace_warnings = metrics_mod.counter(
    "dl4j_tpu_retrace_warnings_total",
    "functions recompiled past the retrace threshold",
    labelnames=("fn",))
_cache_hits = metrics_mod.counter(
    "dl4j_tpu_persistent_cache_hits_total",
    "backend compiles satisfied from the persistent compilation cache "
    "(jax.monitoring cache-retrieval events)")


# ---------------------------------------------------------------------------
# compiled-HLO collective census (shardlint's runtime twin)
# ---------------------------------------------------------------------------

_forced_census: Optional[bool] = None


def configure_census(on: Optional[bool] = None) -> None:
    """Programmatic override of DL4J_TPU_COLLECTIVE_CENSUS (the
    configure(layer_every) shape): True/False force it, None returns
    control to the env gate."""
    global _forced_census
    _forced_census = on


def census_enabled() -> bool:
    if _forced_census is not None:
        return _forced_census
    return envflags.enabled(CENSUS_GATE, False)


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f16": 2, "bf16": 2,
    "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

# one HLO instruction: `%name = <result-shape> <collective-op>(...)`.
# -start covers async forms (the matching -done is a different opcode
# and never matches); the shape group spans tuple results too.
_HLO_COLLECTIVE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?P<shape>\([^)]*\)|\S+)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?\(", re.MULTILINE)

_SHAPE_TOKEN_RE = re.compile(r"(?P<dt>[a-z]+\d*)\[(?P<dims>[0-9,]*)\]")

_REPLICA_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,{} ]*)\}\}")


def _shape_bytes(shape_str: str) -> int:
    """Bytes of an HLO result shape string — `f32[16,128]{1,0}` or a
    tuple `(f32[16]{0}, u32[])`; async -start tuples double-count the
    aliased input element, matching how the op holds both buffers live."""
    total = 0
    for m in _SHAPE_TOKEN_RE.finditer(shape_str):
        nbytes = _DTYPE_BYTES.get(m.group("dt"))
        if nbytes is None:
            continue  # token{1,0} layout suffixes don't match [dims]
        elems = 1
        dims = m.group("dims")
        if dims:
            for d in dims.split(","):
                elems *= int(d)
        total += elems * nbytes
    return total


def _shape_rank(shape_str: str) -> int:
    """Max rank across the tokens of an HLO result shape string (tuple
    results — async -start forms — take the widest element)."""
    rank = 0
    for m in _SHAPE_TOKEN_RE.finditer(shape_str):
        if _DTYPE_BYTES.get(m.group("dt")) is None:
            continue
        dims = m.group("dims")
        rank = max(rank, len(dims.split(",")) if dims else 0)
    return rank


def _groups_cross_hosts(line: str, devices_per_host: Optional[int]) -> bool:
    """Whether an explicit replica_groups={{...}} list puts two devices
    of one group on different hosts (contiguous device-to-host mapping —
    the mesh.build_mesh ordering). Iota-form groups and single-host runs
    classify as ICI."""
    if not devices_per_host or devices_per_host <= 0:
        return False
    m = _REPLICA_GROUPS_RE.search(line)
    if not m:
        return False
    for group in m.group(1).split("}"):
        ids = [int(x) for x in
               group.replace("{", "").replace(" ", "").split(",") if x]
        if len({i // devices_per_host for i in ids}) > 1:
            return True
    return False


def parse_collective_ops(hlo_text: str,
                         devices_per_host: Optional[int] = None
                         ) -> Dict[str, Dict[str, int]]:
    """Collective ops in a compiled HLO module text:
    {kind: {count, bytes, bytes_dcn, bytes_param}} with kind in
    all_gather / all_reduce / reduce_scatter / collective_permute /
    all_to_all. Bytes are the op's per-device RESULT shape
    (SPMD-partitioned modules print shard shapes) — the same accounting
    shardlint's plan uses. ``bytes_param`` is the PARAMETER-PLANE
    subtotal: ops whose result carries no batch dimension (rank <= 2 in
    this framework's [batch, time, features] conventions) — weight
    gathers and gradient reductions, the traffic the static plan
    contracts; higher-rank results are activation traffic the SPMD
    partitioner chose, which the census measures but the plan does not
    promise."""
    out: Dict[str, Dict[str, int]] = {}
    for line in hlo_text.splitlines():
        m = _HLO_COLLECTIVE_RE.match(line)
        if m is None:
            continue
        kind = m.group("op").replace("-", "_")
        nbytes = _shape_bytes(m.group("shape"))
        rec = out.setdefault(kind, {"count": 0, "bytes": 0,
                                    "bytes_dcn": 0, "bytes_param": 0})
        rec["count"] += 1
        rec["bytes"] += nbytes
        if _shape_rank(m.group("shape")) <= 2:
            rec["bytes_param"] += nbytes
        if _groups_cross_hosts(line, devices_per_host):
            rec["bytes_dcn"] += nbytes
    return out


def _devices_per_host() -> Optional[int]:
    """Local device count when the job actually spans processes — the
    contiguous-block host mapping the census classifies DCN traffic by.
    None (everything ICI) in a single-process run."""
    try:
        import jax

        if jax.process_count() > 1:
            return max(1, jax.local_device_count())
    except Exception:
        pass  # jaxlint: disable=JX009 — best-effort topology probe; census falls back to all-ICI
    return None


# ---------------------------------------------------------------------------
# the compile account (always on)
# ---------------------------------------------------------------------------

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_STAGE_KEYS = ("traces", "trace_s", "lower_s", "backend_compiles",
               "backend_compile_s")
_CACHE_KEYS = ("cache_hits", "cache_misses", "cache_retrieval_s",
               "compile_time_saved_s")
_TRACES, _TRACE_S, _LOWER_S, _BACKEND, _BACKEND_S = range(5)
_ZERO_ROW = (0, 0.0, 0.0, 0, 0.0)
_HITS, _MISSES, _READ_S, _SAVED_S = range(4)

# the lowering and the backend name a function `jit(step)` (`pmap(step)`)
# where the trace said `step`
_WRAPPED_NAME_RE = re.compile(r"^\w+\((.*)\)$")


def _plain_name(fun_name) -> str:
    m = _WRAPPED_NAME_RE.match(str(fun_name))
    return m.group(1) if m else str(fun_name)


# a tally: ({function name: the five stage numbers}, the four cache numbers)
_Tally = Tuple[Dict[str, List[float]], List[float]]


def _tally() -> _Tally:
    return {}, [0, 0, 0.0, 0.0]


def _tally_add(into: _Tally, more: _Tally) -> None:
    for name, row in more[0].items():
        dst = into[0].setdefault(name, list(_ZERO_ROW))
        for i, v in enumerate(row):
            dst[i] += v
    for i, v in enumerate(more[1]):
        into[1][i] += v


def _tally_less(now: _Tally, base: _Tally) -> _Tally:
    """`now - base`, functions with nothing new left out (a nanosecond is
    a sum's rounding, not news)."""
    by = {}
    for name, row in now[0].items():
        b = base[0].get(name)
        d = list(row) if b is None else [v - w for v, w in zip(row, b)]
        if any(abs(v) > 1e-9 for v in d):
            by[name] = d
    return by, [v - w for v, w in zip(now[1], base[1])]


def _tally_dict(t: _Tally) -> Dict[str, Any]:
    """The form `fit_log()` and `setup_log()` carry: the stage totals over
    every function, the cache's numbers, and `by_fn`."""
    by = {name: dict(zip(_STAGE_KEYS, row)) for name, row in sorted(t[0].items())}
    out: Dict[str, Any] = {
        k: sum((row[i] for row in t[0].values()), _ZERO_ROW[i])
        for i, k in enumerate(_STAGE_KEYS)}
    out.update(zip(_CACHE_KEYS, t[1]))
    out["by_fn"] = by
    return out


class CompileAccount:
    """Always-on totals of what JAX compiled, from its own monitoring
    events — the `PhaseAccount` of the cold path. Per function name and in
    total: `traces`, `trace_s` (Python tracing to a jaxpr), `lower_s`
    (jaxpr to an MLIR module), `backend_compiles` and `backend_compile_s`
    (what the process waited for the backend OR for the persistent cache:
    JAX fires the event on a cache hit too); in total only, because JAX
    names no function on them: `cache_hits`, `cache_misses`,
    `cache_retrieval_s`, `compile_time_saved_s` (what a hit's compilation
    had cost when it was cached, less the read: negative for a tiny
    program).

    A function is listed under the name its LOWERING gives it, `jit(...)`
    stripped. JAX fires a trace event for every jitted function traced
    inside another (`matmul`, `tanh` inside `step`), whose seconds the
    outer event already holds and which closes before it: so a trace is
    booked when the function it names is then lowered on the same thread,
    and the inner ones, which never are, are dropped at that lowering —
    with whatever a kernel's lowering traced meanwhile. Each second of a
    stage counts once.

    `mark()` / `since(mark)` are `PhaseAccount`'s; `claim(bucket, mark)`
    also adds what `since` returns to a named bucket, so that
    `setup_log()` can say what was compiled in no fit."""

    def __init__(self):
        self._lock = threading.Lock()
        self._now: _Tally = _tally()  # guarded-by: self._lock
        self._claimed: Dict[str, _Tally] = {}  # guarded-by: self._lock
        # `.traces`: {function: seconds} of the traces closed on this
        # thread since its last lowering (a later one of a name wins: the
        # outer function's, where an inner one has its name)
        self._closed = threading.local()

    # -- the listeners ---------------------------------------------------
    def on_duration(self, event: str, seconds: float, fun_name=None,
                    **kw) -> None:
        seconds = float(seconds)
        if event == TRACE_EVENT:
            traces = self._closed.__dict__.setdefault("traces", {})
            traces.pop(fun_name, None)          # re-inserted as the newest
            traces[fun_name] = seconds
        elif event == LOWER_EVENT:
            name = _plain_name(fun_name)
            traces = self._closed.__dict__.pop("traces", {})
            # a `functools.partial` is lowered nameless: the newest trace
            traced = (traces.popitem()[1] if name == "<unknown>" and traces
                      else traces.get(name))
            with self._lock:
                row = self._now[0].setdefault(name, list(_ZERO_ROW))
                row[_LOWER_S] += seconds
                if traced is not None:
                    row[_TRACES] += 1
                    row[_TRACE_S] += traced
        elif event == BACKEND_EVENT:
            with self._lock:
                row = self._now[0].setdefault(_plain_name(fun_name),
                                              list(_ZERO_ROW))
                row[_BACKEND] += 1
                row[_BACKEND_S] += seconds
        elif event == CACHE_READ_EVENT:
            self._add_cache(_READ_S, seconds)
        elif event == CACHE_SAVED_EVENT:
            self._add_cache(_SAVED_S, seconds)

    def on_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            self._add_cache(_HITS, 1)
        elif event == CACHE_MISS_EVENT:
            self._add_cache(_MISSES, 1)

    def _add_cache(self, index: int, value: float) -> None:
        with self._lock:
            self._now[1][index] += value

    # -- readers ---------------------------------------------------------
    def mark(self) -> _Tally:
        with self._lock:
            return ({k: list(v) for k, v in self._now[0].items()},
                    list(self._now[1]))

    def since(self, mark: _Tally) -> Dict[str, Any]:
        """What was added after `mark`: zeros and an empty `by_fn` when
        nothing was traced, lowered or compiled."""
        return _tally_dict(_tally_less(self.mark(), mark))

    def claim(self, bucket: str, mark: _Tally) -> Dict[str, Any]:
        """`since(mark)`, also added to `bucket`'s running sum."""
        delta = _tally_less(self.mark(), mark)
        with self._lock:
            _tally_add(self._claimed.setdefault(bucket, _tally()), delta)
        return _tally_dict(delta)

    def claimed(self, bucket: str) -> Dict[str, Any]:
        with self._lock:
            return _tally_dict(self._claimed.get(bucket, _tally()))

    def outside(self, bucket: str) -> Dict[str, Any]:
        """Everything since the process started that `bucket` did not
        claim."""
        with self._lock:
            base = self._claimed.get(bucket, _tally())
            return _tally_dict(_tally_less(self._now, base))

    def total(self, key: str) -> float:
        """One of the nine totals since the process started."""
        with self._lock:
            if key in _CACHE_KEYS:
                return self._now[1][_CACHE_KEYS.index(key)]
            i = _STAGE_KEYS.index(key)
            return sum(row[i] for row in self._now[0].values())


def _fingerprint(leaves) -> Tuple:
    """Abstract (shape, dtype) tuple over already-flattened call args —
    the jit trace-cache key modulo weak types. Non-arrays hash by value
    (static scalars change the trace too)."""
    out = []
    for a in leaves:
        shape = getattr(a, "shape", None)
        if shape is not None:
            out.append((tuple(shape), str(getattr(a, "dtype", ""))))
        else:
            out.append(a if isinstance(a, (int, float, bool, str,
                                           type(None))) else type(a))
    return tuple(out)


class CompileWatcher:
    """Process-global compile observer. ``enabled`` mirrors the tracer's
    gate — checked once per wrapped call, so the disabled path is the
    raw jitted call plus one property read."""

    def __init__(self):
        self._lock = threading.Lock()
        #: what JAX traced, lowered and compiled, gate on or off
        self.account = CompileAccount()
        # fn name -> {fingerprint: compile-inclusive first-call seconds}
        self._fns: Dict[str, Dict[Tuple, float]] = {}  # guarded-by: self._lock
        self._warned: set = set()  # guarded-by: self._lock
        # fn name -> {kind: {count, bytes, bytes_dcn}} from the census
        self._collectives: Dict[str, Dict[str, Dict[str, int]]] = {}  # guarded-by: self._lock

    @property
    def enabled(self) -> bool:
        return trace_mod.tracer().enabled

    @property
    def threshold(self) -> int:
        return envflags.int_value(RETRACE_GATE, 3)

    def reset(self) -> None:
        with self._lock:
            self._fns.clear()
            self._warned.clear()
            self._collectives.clear()

    # ------------------------------------------------------------------
    def call(self, jitted, name: str, args: tuple, kwargs: dict):
        """The jaxcompat.jit seam: detect trace-cache misses by
        fingerprint, time them, feed the retrace detector. Calls made
        while tracing (the jitted fn nested inside another jit) pass
        straight through — the inner call compiles nothing itself."""
        import jax

        leaves = jax.tree_util.tree_leaves((args, kwargs))
        if any(isinstance(x, jax.core.Tracer) for x in leaves):
            return jitted(*args, **kwargs)
        fp = _fingerprint(leaves)
        with self._lock:
            entry = self._fns.setdefault(name, {})
            seen = fp in entry
        if seen:
            return jitted(*args, **kwargs)
        if census_enabled():
            # BEFORE the call: donate_argnums consumes these buffers
            self._census(jitted, name, args, kwargs)
        t0 = time.perf_counter()
        try:
            return jitted(*args, **kwargs)
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                entry[fp] = dt
                n_traces = len(entry)
            self._on_trace(name, n_traces, dt)

    def _on_trace(self, name: str, n_traces: int, seconds: float) -> None:
        _compiles_total.labels(name).inc()
        tr = trace_mod.tracer()
        tr.add_span("compile", seconds * 1e3, category="compile",
                    fn=name, traces=n_traces)
        if n_traces > self.threshold:
            _retrace_warnings.labels(name).inc()
            tr.add_instant("retrace", category="compile", fn=name,
                           traces=n_traces)
            with self._lock:
                first_warning = name not in self._warned
                self._warned.add(name)
            if first_warning:
                warnings.warn(
                    f"jit function {name!r} retraced {n_traces} times "
                    f"(threshold {self.threshold}): argument shapes/"
                    f"dtypes keep changing — pad/bucket inputs or hoist "
                    f"the changing value out of the traced signature "
                    f"(docs/PROFILING.md)", stacklevel=3)

    def _census(self, jitted, name: str, args: tuple, kwargs: dict) -> None:
        """Lower + compile this exact call and record its collectives.
        A second compile of the same program — the census gate is opt-in
        precisely because of that cost. Never raises: a census failure
        must not break the step it observes."""
        try:
            hlo = jitted.lower(*args, **kwargs).compile().as_text()
            ops = parse_collective_ops(hlo, _devices_per_host())
        except Exception:
            return
        with self._lock:
            cur = self._collectives.setdefault(name, {})
            for kind, rec in ops.items():
                dst = cur.setdefault(kind,
                                     {"count": 0, "bytes": 0,
                                      "bytes_dcn": 0, "bytes_param": 0})
                for k in dst:
                    dst[k] += rec[k]

    def collective_census(self) -> Dict[str, Dict[str, Dict[str, int]]]:
        """Per-watch-name census: {fn: {kind: {count, bytes, bytes_dcn}}}
        (empty until a census-gated trace-cache miss compiles)."""
        with self._lock:
            return {name: {k: dict(v) for k, v in kinds.items()}
                    for name, kinds in sorted(self._collectives.items())}

    def collective_totals(self, name: Optional[str] = None
                          ) -> Dict[str, Dict[str, int]]:
        """Census aggregated over watch names (or one name):
        {kind: {count, bytes, bytes_dcn, bytes_param}} — the shape
        sharding.compare_collectives matches the static plan against."""
        totals: Dict[str, Dict[str, int]] = {}
        with self._lock:
            items = ([self._collectives.get(name, {})] if name is not None
                     else list(self._collectives.values()))
            for kinds in items:
                for kind, rec in kinds.items():
                    dst = totals.setdefault(kind,
                                            {"count": 0, "bytes": 0,
                                             "bytes_dcn": 0,
                                             "bytes_param": 0})
                    for k in dst:
                        dst[k] += rec.get(k, 0)
        return totals

    # ------------------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """Machine-readable state for /profile and the profile CLI. `fns`,
        `seam_compiles` and `retraced_fns` come from the jit seam and need
        the gate; the rest reads the compile account and is true without.
        `backend_compile_seconds` is what the process waited for the
        backend OR the persistent cache (JAX fires the event on a hit
        too); `cache_retrieval_seconds` is the part of it spent reading."""
        with self._lock:
            fns = {name: {"traces": len(fps),
                          "compile_seconds": round(sum(fps.values()), 4)}
                   for name, fps in sorted(self._fns.items())}
            retraced = sorted(self._warned)
        return {
            "fns": fns,
            "collectives": self.collective_census(),
            "seam_compiles": int(sum(f["traces"] for f in fns.values())),
            "backend_compiles": self.compile_count(),
            "backend_compile_seconds": round(
                self.account.total("backend_compile_s"), 4),
            "cache_retrieval_seconds": round(
                self.account.total("cache_retrieval_s"), 4),
            "persistent_cache_hits": self.cache_hit_count(),
            "cold_compiles": self.cold_compile_count(),
            "retraced_fns": retraced,
        }

    def compile_count(self) -> int:
        """XLA backend compilations of this process (the compile account;
        gate on or off)."""
        return int(self.account.total("backend_compiles"))

    def cold_compile_count(self) -> int:
        """Backend compiles that actually RAN XLA. jax fires a
        backend_compile_duration event even when the executable came out
        of the persistent compilation cache (the hit fires its own
        event), so the true cold count is the difference — the number a
        zero-cold-start restart test pins to zero
        (serving/warmstart.py)."""
        return max(0, self.compile_count() - self.cache_hit_count())

    def cache_hit_count(self) -> int:
        """Backend compiles satisfied from the persistent cache."""
        return int(self.account.total("cache_hits"))


_watcher: Optional[CompileWatcher] = None  # guarded-by: _watcher_lock
_watcher_lock = threading.Lock()


def watcher() -> CompileWatcher:
    global _watcher
    w = _watcher  # noqa: DLC002 — double-checked fast path: the pointer read is atomic under the GIL and the slow path re-reads it under _watcher_lock before constructing
    if w is None:
        with _watcher_lock:
            w = _watcher
            if w is None:
                w = _watcher = CompileWatcher()
                _install_monitoring(w.account)
    return w


def _install_monitoring(account: CompileAccount) -> None:
    """Register the two jax.monitoring listeners that feed `account`, once
    per process (from `watcher()`, under its lock). They count with the
    telemetry gate on or off — stages, seconds, function names, cache
    reads: a compilation is a cold path, and the account is what
    `telemetry.fit_log()` reports for every fit (`compiles`, `compile`)
    and `telemetry.setup_log()` for the time before. What still needs the
    gate is the jit seam's own work: argument fingerprints, the retrace
    warning, the `compile` span and the collective census. The three
    Prometheus counters are fed here, beside the account."""
    from jax import monitoring

    def on_duration(event: str, seconds: float, **kw) -> None:
        try:
            account.on_duration(event, seconds, **kw)
            if event == BACKEND_EVENT:
                _backend_compiles.inc()
                _compile_seconds.inc(float(seconds))
            elif event == CACHE_READ_EVENT:
                _cache_hits.inc()
        except Exception:  # a telemetry hook must never break compilation
            pass  # jaxlint: disable=JX009

    def on_event(event: str, **kw) -> None:
        try:
            account.on_event(event, **kw)
        except Exception:  # a telemetry hook must never break compilation
            pass  # jaxlint: disable=JX009

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


# ---------------------------------------------------------------------------
# the set-up account: what the program spent outside any fit
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def init_span():
    """The `init` span of `MultiLayerNetwork.init` / `ComputationGraph.init`
    (a decorator there). What the initialisers compile meanwhile is
    claimed for `setup_log()["init"]["compile"]`."""
    account = watcher().account
    mark = account.mark()
    try:
        with trace_mod.tracer().span("init", category="setup"):
            yield
    finally:
        account.claim("init", mark)


def setup_log() -> Dict[str, Any]:
    """What this process spent in the program outside any fit, gate on or
    off: ``{import_s, init: {calls, total_s, compile}, place: {calls,
    total_s}, compile_outside_fits}``. `import_s` is the package's own
    import, `init` every network's parameter and state initialisation with
    what the initialisers compiled (the `compile` form of `fit_log()`),
    `place` `ParallelWrapper`'s placement of the model on its mesh, and
    `compile_outside_fits` everything JAX traced, lowered or compiled in
    no fit — `init`'s share included, and whatever the caller jitted
    itself. With `fit_log()`'s `t_start_s` and `wall_s` it lays the process
    out in order. docs/TELEMETRY.md "Set-up account"."""
    phases = trace_mod.tracer().account.snapshot()
    account = watcher().account

    def row(name):
        p = phases.get(name, {})
        return {"calls": p.get("calls", 0), "total_s": p.get("total_s", 0.0)}

    return {"import_s": row("import")["total_s"],
            "init": {**row("init"), "compile": account.claimed("init")},
            "place": row("place"),
            "compile_outside_fits": account.outside("fit")}


# ---------------------------------------------------------------------------
# HBM watermark sampling
# ---------------------------------------------------------------------------


def hbm_stats() -> Dict[str, Dict[str, int]]:
    """Per-device memory stats, {} on backends without them (CPU). Never
    raises — introspection must not take down a training loop."""
    try:
        import jax

        out = {}
        for d in jax.local_devices():
            ms = getattr(d, "memory_stats", None)
            if ms is None:
                continue
            stats = ms()
            if stats:
                out[f"{d.platform}:{d.id}"] = dict(stats)
        return out
    except Exception:
        return {}


def sample_hbm(stats: Optional[Dict[str, Dict[str, int]]] = None
               ) -> Dict[str, int]:
    """One watermark sample: publish dl4j_tpu_hbm_bytes{device} gauges
    and return {device: bytes_in_use}. Guarded no-op (empty dict, no
    gauge children) when the backend exposes no memory stats. Pass a
    precomputed ``hbm_stats()`` result to avoid re-querying devices."""
    if stats is None:
        stats = hbm_stats()
    if not stats:
        return {}
    gauge = metrics_mod.gauge(
        "dl4j_tpu_hbm_bytes", "device bytes in use at the last sample",
        labelnames=("device",))
    out = {}
    for dev, ms in stats.items():
        used = int(ms.get("bytes_in_use", 0))
        gauge.labels(dev).set(used)
        out[dev] = used
    return out


class _NullFitIntrospection:
    """Disabled-path singleton: every hook is a no-op (the NULL_SPAN
    pattern — zero allocation per fit/step when telemetry is off)."""

    __slots__ = ()

    def after_step(self):
        pass

    def end(self, model=None):
        pass


NULL_FIT = _NullFitIntrospection()


class FitIntrospection:
    """Per-fit HBM watermark tracker. Created by ``fit_introspection``
    only when the gate is on AND the backend reports memory stats;
    ``end()`` publishes the peak and, when the model's config is
    analyzable, the DLA008/DLA009 predicted working set next to it —
    closing the loop between PR 1's static estimates and reality."""

    def __init__(self):
        self.peak_bytes = 0
        self._sample()

    def _sample(self):
        stats = hbm_stats()
        sample_hbm(stats)
        # prefer the backend's own high-water mark: bytes_in_use at a
        # post-step boundary misses the intra-step activation peak that
        # peak_bytes_in_use natively tracks (PJRT reports it process-
        # cumulative — fine for a watermark, which only ever rises)
        for ms in stats.values():
            used = int(ms.get("peak_bytes_in_use",
                              ms.get("bytes_in_use", 0)))
            if used > self.peak_bytes:
                self.peak_bytes = used

    def after_step(self):
        self._sample()

    def end(self, model=None):
        self._sample()
        metrics_mod.gauge(
            "dl4j_tpu_hbm_peak_bytes",
            "peak per-device bytes in use observed during the last fit"
        ).set(self.peak_bytes)
        predicted = predicted_train_bytes(model)
        if predicted:
            metrics_mod.gauge(
                "dl4j_tpu_hbm_predicted_bytes",
                "analyzer (DLA008) predicted training working set"
            ).set(predicted)
            trace_mod.tracer().add_instant(
                "hbm.watermark", category="memory",
                peak_bytes=self.peak_bytes, predicted_bytes=predicted,
                ratio=round(self.peak_bytes / predicted, 3))


def predicted_train_bytes(model) -> Optional[int]:
    """The analyzer's DLA008 working-set prediction for a model's config
    at its last-seen batch size; None when the config can't be analyzed
    (imported nets with exotic layers etc. — prediction is best-effort)."""
    if model is None:
        return None
    try:
        from deeplearning4j_tpu.analysis import estimate_costs

        batch = int(getattr(model, "last_batch_size", 0)) or 32
        est = estimate_costs(model.conf, batch=batch)
        return int(est["train_bytes"]) if est else None
    except Exception:
        return None


def fit_introspection(model=None):
    """Entry point for the fit loops: the live tracker when telemetry is
    on and the backend has memory stats, else the shared no-op."""
    if not trace_mod.tracer().enabled:
        return NULL_FIT
    if not hbm_stats():  # CPU and friends: guarded no-op
        return NULL_FIT
    return FitIntrospection()


# ---------------------------------------------------------------------------
# sampled per-layer forward/backward spans
# ---------------------------------------------------------------------------

_forced_layer_every: Optional[int] = None


def configure(layer_every: Optional[int] = None) -> None:
    """Programmatic override of DL4J_TPU_PROFILE_LAYERS (the trace-mod
    configure() shape): an int forces the sampling period, None returns
    control to the env gate."""
    global _forced_layer_every
    _forced_layer_every = layer_every


def layer_sample_every() -> int:
    if _forced_layer_every is not None:
        return _forced_layer_every
    return envflags.int_value(LAYER_GATE, 0)


def maybe_layer_spans(model, ds, iteration: int) -> bool:
    """Fit-loop hook: on sampled iterations, time each layer's forward
    and backward eagerly and record spans on the dedicated "layer
    profile" lane. Off by default; one int comparison when off."""
    every = layer_sample_every()
    if not every or iteration % every:
        return False
    tr = trace_mod.tracer()
    if not tr.enabled:
        return False
    try:
        spans = _layer_spans(model, ds)
    except Exception:  # profiling must never break training
        return False
    tr.set_thread_name(_LAYER_TID, "layer profile")
    for name, kind, dur_ms, extra in spans:
        tr.add_span(f"{name}.{kind}", dur_ms, category="layer",
                    thread_id=_LAYER_TID, iteration=iteration, **extra)
    return bool(spans)


def _block(x) -> None:
    import jax

    jax.block_until_ready(x)


def _time_fwd_bwd(apply_fwd, params, x) -> Tuple[float, Optional[float], Any]:
    """(forward ms, backward ms or None, output) for one layer, timed
    eagerly with a completion barrier. Backward is the vjp wrt params
    and input — per-layer cost attribution, not a full-graph gradient."""
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    out = apply_fwd(params, x)
    _block(out)
    fwd_ms = (time.perf_counter() - t0) * 1e3
    bwd_ms: Optional[float] = None
    try:
        t0 = time.perf_counter()
        y, vjp_fn = jax.vjp(apply_fwd, params, x)
        cot = jax.tree_util.tree_map(
            lambda a: jnp.ones(jnp.shape(a), a.dtype), y)
        _block(vjp_fn(cot))
        bwd_ms = (time.perf_counter() - t0) * 1e3
    except Exception:
        # int inputs / non-differentiable layers: forward-only profiling
        pass  # jaxlint: disable=JX009
    return fwd_ms, bwd_ms, out


def _layer_spans(model, ds) -> List[Tuple[str, str, float, dict]]:
    import jax.numpy as jnp

    spans: List[Tuple[str, str, float, dict]] = []

    def record(name, layer_type, fwd_ms, bwd_ms):
        spans.append((name, "fwd", fwd_ms, {"layer": layer_type}))
        if bwd_ms is not None:
            spans.append((name, "bwd", bwd_ms, {"layer": layer_type}))

    if hasattr(model, "layers"):  # MultiLayerNetwork
        x = jnp.asarray(ds.features)
        for i, layer in enumerate(model.layers):
            if i in model.conf.input_preprocessors:
                x = model.conf.input_preprocessors[i].transform(x, None)
            key = f"layer_{i}"
            state = model.state[key]

            def fwd(p, xx, layer=layer, state=state):
                out, _ = layer.apply(p, xx, state=state, train=False,
                                     rng=None, mask=None)
                return out

            fwd_ms, bwd_ms, x = _time_fwd_bwd(fwd, model.params[key], x)
            record(key, type(layer).__name__, fwd_ms, bwd_ms)
        return spans

    # ComputationGraph: walk the topo order like _forward does
    from deeplearning4j_tpu.nn.graph_vertices import LayerVertex

    inputs = (ds.features if isinstance(ds.features, (tuple, list))
              else (ds.features,))
    acts = {name: jnp.asarray(a)
            for name, a in zip(model.conf.network_inputs, inputs)}
    for name in model.topo:
        v = model.conf.vertices[name]
        vin = [acts[x] for x in model.conf.vertex_inputs[name]]
        state = model.state[name]

        def fwd(p, xs, v=v, state=state):
            out, _ = v.apply(p, list(xs), state=state, train=False,
                             rng=None, masks=[None] * len(xs))
            return out

        try:
            fwd_ms, bwd_ms, out = _time_fwd_bwd(fwd, model.params[name],
                                                tuple(vin))
        except Exception:
            break  # output vertices may refuse bare apply; stop cleanly
        kind = (type(v.layer).__name__ if isinstance(v, LayerVertex)
                else type(v).__name__)
        record(name, kind, fwd_ms, bwd_ms)
        acts[name] = out
    return spans


def top_layers(k: int = 5) -> List[Dict[str, Any]]:
    """Top-k layers by total sampled time from the current trace buffer
    (the `profile` CLI's layer table)."""
    totals: Dict[str, Dict[str, float]] = {}
    for r in trace_mod.tracer().records():
        if r.category != "layer" or r.phase != "X":
            continue
        name, _, kind = r.name.rpartition(".")
        t = totals.setdefault(name, {"fwd_ms": 0.0, "bwd_ms": 0.0,
                                     "layer": ""})
        t[f"{kind}_ms"] = t.get(f"{kind}_ms", 0.0) + r.duration_ms
        if r.attrs and r.attrs.get("layer"):
            t["layer"] = r.attrs["layer"]
    rows = [{"name": n, "layer": t["layer"],
             "fwd_ms": round(t["fwd_ms"], 3),
             "bwd_ms": round(t["bwd_ms"], 3),
             "total_ms": round(t["fwd_ms"] + t["bwd_ms"], 3)}
            for n, t in totals.items()]
    rows.sort(key=lambda r: -r["total_ms"])
    return rows[:k]


def reset() -> None:
    """Test hook: drop watcher state (metrics reset separately via
    metrics.registry().reset())."""
    with _watcher_lock:
        w = _watcher
    if w is not None:
        w.reset()


def profile_snapshot() -> Dict[str, Any]:
    """The /profile endpoint payload: phase stats, compile state, MFU
    gauges, HBM watermarks, and the input-pipeline verdict in one
    JSON-ready dict."""
    from deeplearning4j_tpu.telemetry import health as health_mod

    tr = trace_mod.tracer()
    snap = metrics_mod.registry().snapshot()
    hbm = hbm_stats()
    return {
        "enabled": tr.enabled,
        "phases": tr.summary(),
        "compile": watcher().snapshot(),
        # per-fingerprint collective census (empty unless
        # DL4J_TPU_COLLECTIVE_CENSUS / configure_census(True) was on
        # during compilation) — count, bytes, ICI/DCN split per kind
        "collectives": watcher().collective_census(),
        "input_pipeline": health_mod.input_verdict(),
        "mfu": snap.get("dl4j_tpu_mfu"),
        "roofline": snap.get("dl4j_tpu_arithmetic_intensity"),
        "hbm": ({dev: int(ms.get("bytes_in_use", 0))
                 for dev, ms in hbm.items()} if hbm else "unavailable"),
        "hbm_peak_bytes": snap.get("dl4j_tpu_hbm_peak_bytes"),
        "hbm_predicted_bytes": snap.get("dl4j_tpu_hbm_predicted_bytes"),
        "top_layers": top_layers(),
    }
