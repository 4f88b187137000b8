"""Runtime introspection — compile watcher, HBM watermarks, layer spans.

PR 3's telemetry records *when* things happen; this module watches the
layer that determines TPU performance: XLA compilation, device memory,
and the per-layer cost structure of a step. Three instruments, all gated
by ``DL4J_TPU_TELEMETRY`` (the span gate — introspection IS spans+gauges):

  CompileWatcher   counts compilations and compile seconds two ways:
                   (a) a ``jax.monitoring`` duration listener (fires for
                   EVERY backend compile in the process, including raw
                   ``jax.jit`` uses the seam below doesn't cover), and
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
fingerprint sets, no metric children allocated.
"""
from __future__ import annotations

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
        """Machine-readable state for /profile and the profile CLI."""
        with self._lock:
            fns = {name: {"traces": len(fps),
                          "compile_seconds": round(sum(fps.values()), 4)}
                   for name, fps in sorted(self._fns.items())}
            retraced = sorted(self._warned)
        return {
            "fns": fns,
            "collectives": self.collective_census(),
            "seam_compiles": int(sum(f["traces"] for f in fns.values())),
            "backend_compiles": int(_backend_compiles.value),
            "backend_compile_seconds": round(_compile_seconds.value, 4),
            "persistent_cache_hits": int(_cache_hits.value),
            "cold_compiles": self.cold_compile_count(),
            "retraced_fns": retraced,
        }

    def compile_count(self) -> int:
        """XLA backend compilations of this process (jax.monitoring; gate
        on or off). Only where the listener could not be registered, the
        jit seam's count of trace-cache misses, which needs the gate."""
        if _monitoring_installed:  # noqa: DLC002 — written once, under _watcher_lock, before any watcher exists
            return int(_backend_compiles.value)
        return self.snapshot()["seam_compiles"]

    def cold_compile_count(self) -> int:
        """Backend compiles that actually RAN XLA. jax fires a
        backend_compile_duration event even when the executable came out
        of the persistent compilation cache (the retrieval also fires a
        cache-retrieval event), so the true cold count is the difference
        — the number a zero-cold-start restart test pins to zero
        (serving/warmstart.py)."""
        return max(0, int(_backend_compiles.value) - int(_cache_hits.value))

    def cache_hit_count(self) -> int:
        """Backend compiles satisfied from the persistent cache."""
        return int(_cache_hits.value)


_watcher: Optional[CompileWatcher] = None  # guarded-by: _watcher_lock
_watcher_lock = threading.Lock()
_monitoring_installed = False  # guarded-by: _watcher_lock


def watcher() -> CompileWatcher:
    global _watcher
    w = _watcher  # noqa: DLC002 — double-checked fast path: the pointer read is atomic under the GIL and the slow path re-reads it under _watcher_lock before constructing
    if w is None:
        with _watcher_lock:
            w = _watcher
            if w is None:
                w = _watcher = CompileWatcher()
                _install_monitoring()
    return w


def _install_monitoring() -> None:
    """Register the jax.monitoring compile-duration listener once per
    process. It counts with the telemetry gate on or off: a compilation
    is a cold path, and `compile_count()` is what `telemetry.fit_log()`
    reports for every fit."""
    global _monitoring_installed
    if _monitoring_installed:  # noqa: DLC002 — only reachable from watcher(), which already holds _watcher_lock around the call
        return
    try:
        from jax import monitoring
    except ImportError:  # pragma: no cover - every supported jax has it
        return

    def _on_duration(name: str, seconds: float, **kw) -> None:
        try:
            if name.endswith("backend_compile_duration"):
                _backend_compiles.inc()
                _compile_seconds.inc(float(seconds))
            elif "cache_retrieval_time" in name:
                # /jax/compilation_cache/cache_retrieval_time_sec: this
                # backend compile was a persistent-cache disk read — its
                # backend_compile_duration event fires too, so cold
                # compiles = backend_compiles - cache_hits
                _cache_hits.inc()
        except Exception:  # a telemetry hook must never break compilation
            pass  # jaxlint: disable=JX009

    try:
        monitoring.register_event_duration_secs_listener(_on_duration)
        _monitoring_installed = True  # noqa: DLC002 — only reachable from watcher(), which already holds _watcher_lock around the call
    except Exception:  # pragma: no cover - defensive: API drift
        pass  # jaxlint: disable=JX009 — jax.monitoring registration optional


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
