"""Unified telemetry core — spans + metrics for every training path.

The reference stack's observability story is scattered across
PerformanceListener (throughput logs), StatsListener→StatsStorage (the UI
feed), and Spark ``EventStats`` HTML timelines (SURVEY.md §5). TensorFlow
(Abadi et al., 1605.08695) shows the payoff of making step-level tracing
and metrics first-class in the training system itself. This package is
that layer for the TPU build:

  trace    Tracer — thread-safe context-manager/decorator spans over a
           bounded ring buffer, exported losslessly as Chrome trace-event
           JSON (opens in Perfetto / chrome://tracing); merges
           distributed ``TrainingStats``/``EventStats`` timelines into
           the same trace.
  metrics  MetricsRegistry — process-global counters/gauges/histograms
           with label support, rendered in Prometheus text exposition
           (scrape ``/metrics`` on ui/server.py).

The span ring is gated by ``DL4J_TPU_TELEMETRY`` (through
util/envflags.py, jaxlint JX001): when the gate is off no span record is
allocated. Two sinks of the same ``tracer().span(...)`` seam are always
on and cost the instrumented hot loops (MultiLayerNetwork.fit /
ComputationGraph.fit / ParallelWrapper.fit) a few microseconds a step:
a ``jax.profiler`` annotation (``dl4j.<name>``, a no-op outside a
profiler session) and the phase account behind ``fit_log()`` — per fit,
the calls, seconds and bytes of ``etl`` / ``put`` / ``dispatch`` /
``score_wait`` / ``listeners``; the cold path's ``import`` / ``init`` /
``place`` go through the same seam (``setup_log()``). Metrics at resilience sites (checkpoint
writes, retries, sentry trips, chaos injections) are always live: they
fire on cold failure/IO paths where a dict update is free, and a crash
post-mortem must not depend on a gate having been set beforehand.

PR 4 adds the runtime-introspection layer on the same gate:

  introspect  compile watcher (the util.jaxcompat.jit seam) with a
              retrace detector, over the ALWAYS-ON compile account
              (jax.monitoring: trace / lower / backend-or-cache seconds
              by function, cache hits and misses — ``fit_log()``'s
              ``compile``, and ``setup_log()``: import, ``init``,
              ``place`` and what was compiled in no fit), HBM watermark sampling
              (guarded no-op on CPU) with predicted-vs-actual against
              the PR 1 analyzer, and sampled per-layer fwd/bwd spans
              (``DL4J_TPU_PROFILE_LAYERS``).
  profiler    cost/MFU engine: XLA ``cost_analysis`` (DLA008 fallback)
              over measured step medians -> ``dl4j_tpu_mfu`` gauge +
              roofline compute/memory-bound classification. Drives the
              ``profile`` CLI subcommand and the ``/profile`` endpoint.

PR 10 adds the correlation + alerting layer on the same gate:

  context     TraceContext — one trace_id per request/fit, propagated
              contextvars-first with an explicit attach/detach contract
              for thread handoffs; the Tracer stamps the active ids
              onto every span/instant it emits.
  slo         SLO burn-rate engine — declarative objectives evaluated
              as fast+slow multi-window burn rates over the
              MetricsRegistry; firing episodes tick
              ``dl4j_tpu_slo_burn_alerts_total``, write one flight
              bundle carrying the offending trace ids, and degrade
              ``/healthz``. Pull-driven: ``slo`` CLI / ``/slo``.

PR 5 adds the on-call layer on the same gate:

  health      training health monitor — per-fit stall-watchdog
              heartbeats (``DL4J_TPU_STALL_TIMEOUT``), straggler skew
              over per-worker lanes
              (``DL4J_TPU_STRAGGLER_RATIO``), prefetch queue-depth/wait
              accounting and the input-bound vs compute-bound
              ``input_verdict()``. Serves ``/healthz`` on ui/server.py.
  flight      black-box flight recorder — on an unhandled fit exception,
              sentry trip, or stall, atomically writes a postmortem
              bundle (trace + metrics + traceback + env + runtime +
              analyzer estimates + checkpoint manifest) under
              ``DL4J_TPU_FLIGHT_DIR``; ``postmortem`` CLI inspects them.

This PR adds the federation layer on the same gate:

  export     FrameExporter — versioned self-describing telemetry frames
             (cumulative metrics snapshot + trace-ring delta via a
             per-source cursor + health verdict + knob provenance +
             flight-bundle index), per-source sequence numbers, optional
             file spooling for cross-process shipping.
  aggregate  FleetCollector — pull-driven merge of frames from many
             hosts/replicas into ONE registry (exactly-once counters,
             per-source gauges + fleet min/max/sum, bucket-validated
             histogram merge), ONE Chrome trace (lane group per host,
             cross-host trace_id flows intact, clock-skew stamped), and
             a federated second SloEngine instance over the aggregate.
             Serves ``/fleet/*`` on ui/server.py; ``fleet`` CLI.

Architecture, env gates, Perfetto walkthrough: docs/TELEMETRY.md; how to
read MFU/roofline/watermark numbers: docs/PROFILING.md; the stall/
straggler/flight-recorder on-call story: docs/HEALTH.md.
"""
from deeplearning4j_tpu.telemetry.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    counter,
    gauge,
    histogram,
    registry,
    render_prometheus,
)
from deeplearning4j_tpu.telemetry.trace import (  # noqa: F401
    TELEMETRY_GATE,
    Tracer,
    configure,
    device_scope,
    fit_log,
    traced,
    tracer,
)
from deeplearning4j_tpu.telemetry.context import (  # noqa: F401
    TraceContext,
    activate,
    attach,
    current,
    current_trace_id,
    detach,
    new_trace,
)
from deeplearning4j_tpu.telemetry.slo import (  # noqa: F401
    Selector,
    SloEngine,
    SloRule,
    default_rules,
)
from deeplearning4j_tpu.telemetry.introspect import (  # noqa: F401
    CompileWatcher,
    fit_introspection,
    hbm_stats,
    maybe_layer_spans,
    profile_snapshot,
    sample_hbm,
    setup_log,
    watcher,
)
from deeplearning4j_tpu.telemetry.health import (  # noqa: F401
    HealthMonitor,
    fit_health,
    healthz,
    input_verdict,
)
from deeplearning4j_tpu.telemetry.flight import (  # noqa: F401
    dump as flight_dump,
    install_faulthandler,
    list_bundles,
    load_bundle,
)
from deeplearning4j_tpu.telemetry.export import (  # noqa: F401
    FRAME_VERSION,
    FrameExporter,
    exporter,
)
from deeplearning4j_tpu.telemetry.aggregate import (  # noqa: F401
    FleetCollector,
    collector,
    deregister_replica,
    register_local_host,
    register_replica,
)
