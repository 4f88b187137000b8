"""Unified telemetry core: Tracer spans (nesting, thread-safety, ring
buffer, Chrome trace-event schema, EventStats merge), MetricsRegistry
(Prometheus exposition of counters/gauges/histograms), the instrumented
fit paths (etl/step spans + lifecycle callbacks), resilience counters
under DL4J_TPU_CHAOS faults, the /metrics + /trace endpoints, the trace
CLI, and the disabled-mode no-op contract (ISSUE 3 acceptance)."""
import json
import re
import threading
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn import updaters
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import Dense, Output
from deeplearning4j_tpu.optimize.listeners import (
    ProfilerListener,
    TrainingListener,
)
from deeplearning4j_tpu.resilience import (
    ChaosError,
    CheckpointManager,
    DivergenceSentry,
    reset_fault_points,
)
from deeplearning4j_tpu.telemetry import metrics as metrics_mod
from deeplearning4j_tpu.telemetry import trace as trace_mod


def _net(seed=1):
    conf = NeuralNetConfiguration(
        seed=seed, updater=updaters.Adam(learning_rate=5e-3),
    ).list([
        Dense(n_out=16, activation="relu"),
        Output(n_out=3, loss="mcxent"),
    ]).set_input_type(it.feed_forward(4))
    return MultiLayerNetwork(conf).init()


@pytest.fixture(autouse=True)
def _clean_telemetry(monkeypatch):
    """Each test starts gate-off with empty global buffers; chaos gates
    and fault-point counters are re-armed around every case."""
    monkeypatch.delenv("DL4J_TPU_TELEMETRY", raising=False)
    monkeypatch.delenv("DL4J_TPU_CHAOS", raising=False)
    trace_mod.configure(enabled=None)
    trace_mod.tracer().clear()
    metrics_mod.registry().reset()
    reset_fault_points()
    yield
    trace_mod.configure(enabled=None)
    trace_mod.tracer().clear()
    metrics_mod.registry().reset()
    reset_fault_points()


# ===========================================================================
# Tracer core
# ===========================================================================


class TestTracer:
    def test_span_nesting_records_both(self):
        tr = trace_mod.Tracer(enabled=True)
        with tr.span("outer", category="t") as s:
            s.set(step=3)
            with tr.span("inner", category="t"):
                pass
        recs = {r.name: r for r in tr.records()}
        assert set(recs) == {"outer", "inner"}
        # inner closes first and nests inside outer on the same lane
        assert recs["inner"].duration_ms <= recs["outer"].duration_ms
        assert recs["inner"].thread_id == recs["outer"].thread_id
        assert recs["inner"].start >= recs["outer"].start
        assert recs["outer"].attrs == {"step": 3}

    def test_decorator_span(self):
        trace_mod.configure(enabled=True)
        tr = trace_mod.tracer()

        @trace_mod.traced("work", category="t")
        def work(x):
            return x + 1

        assert work(1) == 2
        assert [r.name for r in tr.records()] == ["work"]

    def test_thread_safety(self):
        tr = trace_mod.Tracer(capacity=100_000, enabled=True)
        barrier = threading.Barrier(8)  # all 8 alive at once: distinct ids

        def worker():
            barrier.wait()
            for _ in range(200):
                with tr.span("w"):
                    pass

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(tr) == 8 * 200
        assert len({r.thread_id for r in tr.records()}) == 8

    def test_ring_buffer_bounds_and_drop_count(self):
        tr = trace_mod.Tracer(capacity=4, enabled=True)
        for i in range(10):
            tr.add_span(f"s{i}", 1.0)
        assert len(tr) == 4
        assert tr.dropped == 6
        # newest survive (ring semantics, lossless over the buffer)
        assert [r.name for r in tr.records()] == ["s6", "s7", "s8", "s9"]

    def test_chrome_trace_schema_roundtrip(self, tmp_path):
        tr = trace_mod.Tracer(enabled=True)
        with tr.span("step", category="train"):
            pass
        tr.add_span("etl", 2.5, category="data", batch=32)
        path = str(tmp_path / "trace.json")
        tr.export_chrome(path)
        with open(path) as f:
            doc = json.load(f)
        assert doc["displayTimeUnit"] == "ms"
        evs = doc["traceEvents"]
        assert len(evs) == 2
        for ev in evs:
            assert ev["ph"] == "X"
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] > 0
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
            assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        by_name = {e["name"]: e for e in evs}
        assert by_name["etl"]["args"] == {"batch": 32}
        assert by_name["etl"]["dur"] == pytest.approx(2500, rel=1e-6)

    def test_merge_training_stats_object_and_dict(self):
        from deeplearning4j_tpu.distributed.stats import TrainingStats

        st = TrainingStats()
        with st.time_phase("fit", worker=0):
            pass
        with st.time_phase("fit", worker=1):
            pass
        with st.time_phase("broadcast", bytes=128):
            pass
        tr = trace_mod.Tracer(enabled=True)
        assert tr.merge_training_stats(st) == 3
        assert tr.merge_training_stats(st.to_json()) == 3
        doc = tr.to_chrome_trace()
        lanes = {e["args"]["name"] for e in doc["traceEvents"]
                 if e["ph"] == "M"}
        assert lanes == {"master", "worker 0", "worker 1"}
        names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert names == {"fit", "broadcast"}
        # worker events sit on distinct lanes
        tids = {e["tid"] for e in doc["traceEvents"]
                if e["ph"] == "X" and e["name"] == "fit"}
        assert len(tids) == 2

    def test_training_stats_export_chrome(self, tmp_path):
        from deeplearning4j_tpu.distributed.stats import TrainingStats

        st = TrainingStats()
        with st.time_phase("aggregate"):
            pass
        path = st.export_chrome(str(tmp_path / "dist.json"))
        with open(path) as f:
            doc = json.load(f)
        assert any(e.get("name") == "aggregate" for e in doc["traceEvents"])

    def test_summary_medians(self):
        tr = trace_mod.Tracer(enabled=True)
        for d in (1.0, 3.0, 100.0):
            tr.add_span("step", d)
        s = tr.summary()["step"]
        assert s["count"] == 3
        assert s["p50_ms"] == 3.0
        assert s["total_ms"] == 104.0
        assert s["max_ms"] == 100.0

    def test_env_gate_controls_global_tracer(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        assert trace_mod.tracer().enabled
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "0")
        assert not trace_mod.tracer().enabled
        # programmatic override beats the env; None returns to it
        trace_mod.configure(enabled=True)
        assert trace_mod.tracer().enabled
        trace_mod.configure(enabled=None)
        assert not trace_mod.tracer().enabled

    def test_capacity_resize_keeps_forced_enablement(self):
        trace_mod.configure(enabled=True)
        trace_mod.configure(capacity=128)  # resize only: no gate change
        assert trace_mod.tracer().enabled
        assert trace_mod.tracer().capacity == 128

    def test_disabled_tracer_allocates_no_span_records(self):
        """ISSUE 3 acceptance: the disabled span() path returns the shared
        no-op singleton — zero records, zero growth."""
        tr = trace_mod.Tracer(enabled=False)
        s1 = tr.span("a", category="x")
        s2 = tr.span("b")
        assert s1 is s2 is trace_mod.NULL_SPAN
        with s1:
            pass
        tr.add_span("c", 1.0)
        assert len(tr) == 0 and tr.dropped == 0


# ===========================================================================
# MetricsRegistry / Prometheus exposition
# ===========================================================================


class TestMetrics:
    def test_counter_gauge_exposition(self):
        reg = metrics_mod.MetricsRegistry()
        c = reg.counter("dl4j_test_total", "a counter", labelnames=("op",))
        c.labels("read").inc()
        c.labels("read").inc(2)
        c.labels(op="write").inc()
        g = reg.gauge("dl4j_test_gauge", "a gauge")
        g.set(1.5)
        g.inc()
        g.dec(0.5)
        text = reg.render()
        assert "# HELP dl4j_test_total a counter" in text
        assert "# TYPE dl4j_test_total counter" in text
        assert 'dl4j_test_total{op="read"} 3' in text
        assert 'dl4j_test_total{op="write"} 1' in text
        assert "dl4j_test_gauge 2" in text
        with pytest.raises(ValueError, match="only go up"):
            c.labels("read").inc(-1)

    def test_histogram_exposition_parses(self):
        reg = metrics_mod.MetricsRegistry()
        h = reg.histogram("dl4j_test_seconds", "dur", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        lines = [ln for ln in reg.render().splitlines()
                 if not ln.startswith("#")]
        series = {}
        for ln in lines:
            m = re.fullmatch(
                r'([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? '
                r'(-?[0-9.eE+]+|\+Inf|NaN)', ln)
            assert m, f"unparsable exposition line: {ln!r}"
            series[(m.group(1), m.group(2))] = m.group(3)
        assert series[("dl4j_test_seconds_bucket", 'le="0.1"')] == "1"
        assert series[("dl4j_test_seconds_bucket", 'le="1"')] == "2"
        assert series[("dl4j_test_seconds_bucket", 'le="+Inf"')] == "3"
        assert series[("dl4j_test_seconds_count", None)] == "3"
        assert float(series[("dl4j_test_seconds_sum", None)]) == \
            pytest.approx(5.55)

    def test_label_escaping(self):
        reg = metrics_mod.MetricsRegistry()
        c = reg.counter("esc_total", "", labelnames=("msg",))
        c.labels('say "hi"\nback\\slash').inc()
        line = [ln for ln in reg.render().splitlines()
                if ln.startswith("esc_total{")][0]
        assert line == 'esc_total{msg="say \\"hi\\"\\nback\\\\slash"} 1'

    def test_registry_idempotent_and_type_guard(self):
        reg = metrics_mod.MetricsRegistry()
        a = reg.counter("same_total", "x")
        assert reg.counter("same_total", "x") is a
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("same_total")
        with pytest.raises(ValueError, match="already registered"):
            reg.counter("same_total", "x", labelnames=("op",))

    def test_histogram_bucket_mismatch_raises(self):
        reg = metrics_mod.MetricsRegistry()
        reg.histogram("b_seconds", "", buckets=(0.1, 1.0))
        with pytest.raises(ValueError, match="buckets"):
            reg.histogram("b_seconds", "", buckets=(0.5, 5.0))
        # same bounds re-registers fine
        assert reg.histogram("b_seconds", "", buckets=(1.0, 0.1))

    def test_reset_keeps_registration(self):
        reg = metrics_mod.MetricsRegistry()
        c = reg.counter("r_total", "", labelnames=("k",))
        c.labels("a").inc(5)
        reg.reset()
        assert c.labels("a").value == 0
        c.labels("a").inc()  # the pre-reset handle stays live
        assert reg.snapshot()["r_total"] == {"k=a": 1.0}

    def test_unlabeled_use_of_labeled_metric_raises(self):
        reg = metrics_mod.MetricsRegistry()
        c = reg.counter("l_total", "", labelnames=("op",))
        with pytest.raises(ValueError, match="labels"):
            c.inc()


# ===========================================================================
# instrumented fit paths + lifecycle SPI
# ===========================================================================


class _Lifecycle(TrainingListener):
    def __init__(self):
        self.events = []

    def on_fit_start(self, model):
        self.events.append("fit_start")

    def on_fit_end(self, model):
        self.events.append("fit_end")

    def on_epoch_start(self, model, epoch):
        self.events.append("epoch_start")

    def on_epoch_end(self, model, epoch):
        self.events.append("epoch_end")


class TestFitInstrumentation:
    def test_mln_fit_emits_etl_and_step_spans(self, iris_like, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        tr = trace_mod.tracer()
        net = _net()
        lc = _Lifecycle()
        net.set_listeners(lc)
        net.fit(ListDataSetIterator(iris_like, batch=30), epochs=2)
        names = [r.name for r in tr.records()]
        assert names.count("step") == 10  # 5 batches x 2 epochs
        assert names.count("etl") == 10
        assert lc.events[0] == "fit_start" and lc.events[-1] == "fit_end"
        assert lc.events.count("fit_start") == 1
        assert lc.events.count("epoch_start") == 2

    def test_graph_fit_lifecycle_and_spans(self, iris_like, monkeypatch):
        from deeplearning4j_tpu.models import ComputationGraph

        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        conf = (NeuralNetConfiguration(
                    seed=1, updater=updaters.Adam(learning_rate=5e-3))
                .graph()
                .add_inputs("in")
                .add_layer("d", Dense(n_out=8, activation="relu"), "in")
                .add_layer("out", Output(n_out=3, loss="mcxent"), "d")
                .set_outputs("out")
                .set_input_types(it.feed_forward(4)))
        g = ComputationGraph(conf).init()
        lc = _Lifecycle()
        g.listeners = [lc]
        tr = trace_mod.tracer()
        g.fit(ListDataSetIterator(iris_like, batch=50), epochs=1)
        names = [r.name for r in tr.records()]
        assert names.count("step") == 3
        assert lc.events[0] == "fit_start" and lc.events[-1] == "fit_end"

    def test_disabled_fit_allocates_no_spans(self, iris_like, monkeypatch):
        """ISSUE 3 acceptance: DL4J_TPU_TELEMETRY=0 -> the instrumented
        fit path records nothing (no span records allocated)."""
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "0")
        tr = trace_mod.tracer()
        tr.clear()
        net = _net()
        net.fit(ListDataSetIterator(iris_like, batch=30), epochs=2)
        assert len(tr) == 0 and tr.dropped == 0

    def test_on_fit_end_failure_never_masks_training_error(self, iris_like):
        """A raising on_fit_end must not replace an in-flight resumable
        error (the finally-path dispatch is best-effort), and must not
        fail a clean fit either."""
        from deeplearning4j_tpu.resilience import ChaosDataSetIterator

        class BadFlush(TrainingListener):
            def on_fit_end(self, model):
                raise RuntimeError("flush failed")

        net = _net()
        net.set_listeners(BadFlush())
        chaotic = ChaosDataSetIterator(
            ListDataSetIterator(iris_like, batch=30), fail_at=(2,))
        with pytest.raises(ChaosError):  # NOT the RuntimeError
            net.fit(chaotic, epochs=1)
        net2 = _net()
        net2.set_listeners(BadFlush())
        net2.fit(iris_like.features, iris_like.labels)  # clean fit survives
        assert np.isfinite(net2.score_)

    def test_profiler_listener_flushed_by_on_fit_end(self, iris_like,
                                                     tmp_path, monkeypatch):
        """A trace window straddling the end of training is flushed by the
        lifecycle callback, not left open until GC."""
        lst = ProfilerListener(str(tmp_path), start_iteration=2,
                               num_iterations=10**6)
        stopped = []
        monkeypatch.setattr(lst, "_stop", lambda: stopped.append(True))
        lst._active = True  # simulate an open trace window
        net = _net()
        net.set_listeners(lst)
        net.fit(iris_like.features, iris_like.labels)
        assert stopped  # on_fit_end flushed the open window
        lst._active = False  # silence the GC-time real _stop


# ===========================================================================
# resilience counters under chaos + the acceptance arc
# ===========================================================================


class TestResilienceTelemetry:
    def test_parallel_fit_under_chaos_traces_and_counts(
            self, tmp_path, iris_like, monkeypatch):
        """ISSUE 3 acceptance: a ParallelWrapper.fit run under
        DL4J_TPU_CHAOS yields (a) a schema-valid Chrome trace with
        etl/step/checkpoint spans and (b) non-zero retry/sentry-relevant
        series in the Prometheus exposition."""
        from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper

        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        monkeypatch.setenv("DL4J_TPU_RETRY_BACKOFF", "0")
        monkeypatch.setenv("DL4J_TPU_CHAOS",
                           "checkpoint_write@1,collective@7")
        reset_fault_points()
        tr = trace_mod.tracer()
        cm = CheckpointManager(str(tmp_path))
        it_ = ListDataSetIterator(iris_like, batch=30)  # 5 batches/epoch
        net = _net()
        with pytest.raises(ChaosError):
            ParallelWrapper(net, mesh_spec=MeshSpec(data=8)).fit(
                it_, epochs=2, checkpoint_manager=cm)
        monkeypatch.delenv("DL4J_TPU_CHAOS")
        reset_fault_points()
        resumed = _net(seed=42)
        ParallelWrapper(resumed, mesh_spec=MeshSpec(data=8)).fit(
            it_, epochs=2, checkpoint_manager=cm)
        assert resumed.epoch == 2

        # (a) chrome trace with etl/step/checkpoint spans, schema-valid
        doc = tr.to_chrome_trace()
        assert isinstance(doc["traceEvents"], list)
        names = set()
        for ev in doc["traceEvents"]:
            assert {"name", "ph", "pid", "tid"} <= set(ev)
            if ev["ph"] == "X":
                assert ev["dur"] >= 0 and ev["ts"] > 0
                names.add(ev["name"])
        assert {"etl", "step", "checkpoint.write",
                "checkpoint.restore"} <= names

        # (b) non-zero resilience series in the exposition
        text = metrics_mod.render_prometheus()
        assert re.search(
            r'dl4j_tpu_retry_attempts_total\{error="ChaosError"\} [1-9]',
            text)
        assert re.search(
            r'dl4j_tpu_checkpoint_write_seconds_count [1-9]', text)
        assert re.search(
            r'dl4j_tpu_chaos_injections_total\{point="checkpoint_write"\}'
            r' [1-9]', text)
        assert re.search(
            r'dl4j_tpu_chaos_injections_total\{point="collective"\} [1-9]',
            text)

    def test_sentry_trip_counters(self, iris_like):
        sentry = DivergenceSentry(policy="skip_batch", max_rollbacks=2,
                                  snapshot_every=1)
        net = _net()
        net.fit(iris_like.features, iris_like.labels)  # seeds the snapshot
        sentry.iteration_done(net, 1, 0.5)             # takes a snapshot
        sentry.iteration_done(net, 2, float("nan"))    # trips + restores
        text = metrics_mod.render_prometheus()
        assert 'dl4j_tpu_sentry_trips_total{policy="skip_batch"} 1' in text
        assert "dl4j_tpu_sentry_rollbacks_total 1" in text

    def test_retry_exhaustion_counter(self):
        from deeplearning4j_tpu.resilience import retry_call

        def always_fails():
            raise OSError("nope")

        with pytest.raises(OSError):
            retry_call(always_fails, attempts=3, backoff=0)
        snap = metrics_mod.registry().snapshot()
        assert snap["dl4j_tpu_retry_attempts_total"]["error=OSError"] == 3.0
        assert snap["dl4j_tpu_retry_exhausted_total"] == 1.0

    def test_checkpoint_write_bytes_counter(self, tmp_path, iris_like):
        net = _net()
        net.fit(iris_like.features, iris_like.labels)
        cm = CheckpointManager(str(tmp_path))
        path = cm.save(net)
        import os

        snap = metrics_mod.registry().snapshot()
        assert snap["dl4j_tpu_checkpoint_write_bytes_total"] == \
            os.path.getsize(path)
        assert snap["dl4j_tpu_checkpoint_write_seconds"]["count"] == 1


# ===========================================================================
# surfacing: /metrics + /trace endpoints, trace CLI
# ===========================================================================


class TestSurfacing:
    @pytest.fixture()
    def server(self):
        from deeplearning4j_tpu.ui.server import UIServer

        s = UIServer(port=0)
        yield s
        s.stop()

    def test_metrics_endpoint_prometheus(self, server):
        metrics_mod.counter("dl4j_tpu_endpoint_test_total", "t").inc(7)
        with urllib.request.urlopen(server.url() + "/metrics") as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            body = r.read().decode()
        assert "dl4j_tpu_endpoint_test_total 7" in body
        assert "# TYPE dl4j_tpu_endpoint_test_total counter" in body

    def test_trace_endpoint_chrome_json(self, server, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        with trace_mod.tracer().span("served", category="t"):
            pass
        with urllib.request.urlopen(server.url() + "/trace") as r:
            doc = json.loads(r.read())
        assert any(e.get("name") == "served" for e in doc["traceEvents"])

    def test_cli_trace_export_and_summary(self, tmp_path, capsys):
        from deeplearning4j_tpu.cli import main
        from deeplearning4j_tpu.distributed.stats import TrainingStats

        st = TrainingStats()
        with st.time_phase("fit", worker=0):
            pass
        with st.time_phase("aggregate"):
            pass
        stats_path = str(tmp_path / "stats.json")
        st.export_json(stats_path)
        out_path = str(tmp_path / "trace.json")
        assert main(["trace", "export", "--stats", stats_path,
                     "--out", out_path]) == 0
        with open(out_path) as f:
            doc = json.load(f)
        assert {e["name"] for e in doc["traceEvents"]
                if e.get("ph") == "X"} == {"fit", "aggregate"}
        capsys.readouterr()
        # summary works on BOTH formats
        assert main(["trace", "summary", "--file", out_path]) == 0
        assert "aggregate" in capsys.readouterr().out
        assert main(["trace", "summary", "--file", stats_path,
                     "--json"]) == 0
        parsed = json.loads(capsys.readouterr().out)
        assert parsed["fit"]["count"] == 1
        # empty input is an error, not a silent success
        empty = tmp_path / "empty.json"
        empty.write_text('{"events": []}')
        assert main(["trace", "export", "--stats", str(empty),
                     "--out", out_path]) == 1


# ===========================================================================
# the one span seam (ISSUE 24): profiler annotations, the always-on phase
# account, fit_log()
# ===========================================================================

WINDOW_GATE = "DL4J_TPU" "_STEP_WINDOW"  # parse-time concat: JX001 fixture
LEAF_PHASES = ("etl", "put", "dispatch", "score_wait", "listeners")


def _graph():
    from deeplearning4j_tpu.models import ComputationGraph

    conf = (NeuralNetConfiguration(
                seed=1, updater=updaters.Adam(learning_rate=5e-3))
            .graph()
            .add_inputs("in")
            .add_layer("d", Dense(n_out=8, activation="relu"), "in")
            .add_layer("out", Output(n_out=3, loss="mcxent"), "d")
            .set_outputs("out")
            .set_input_types(it.feed_forward(4)))
    return ComputationGraph(conf).init()


def _fit_paths():
    from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper

    return {
        "MultiLayerNetwork.fit": lambda: _net(),
        "ComputationGraph.fit": lambda: _graph(),
        "ParallelWrapper.fit": lambda: ParallelWrapper(
            _net(), mesh_spec=MeshSpec(data=8)),
    }


class TestSpanSeam:
    def test_account_is_on_with_the_gate_off_and_sums_bytes(self):
        tr = trace_mod.tracer()
        assert not tr.enabled
        mark = tr.account.mark()
        with tr.span("seam.a", category="t", bytes=5):
            with tr.span("seam.b", bytes=2.5):
                pass
        with tr.span("seam.a", bytes="not a number"):
            pass
        tr.add_span("seam.c", 3.0, bytes=7)   # after the fact: account too
        got = tr.account.since(mark)
        assert got["seam.a"]["calls"] == 2 and got["seam.a"]["bytes"] == 5
        assert got["seam.b"]["bytes"] == 2.5
        assert got["seam.c"]["total_s"] == pytest.approx(3e-3)
        assert got["seam.a"]["max_s"] <= got["seam.a"]["total_s"]
        assert len(tr) == 0 and tr.dropped == 0   # and no SpanRecord
        # process totals keep counting across marks
        assert tr.account.snapshot()["seam.a"]["calls"] >= 2

    def test_spanned_opens_the_span_while_next_runs(self):
        import time as time_mod

        def slow():
            for i in range(3):
                time_mod.sleep(0.01)
                yield i

        tr = trace_mod.tracer()
        mark = tr.account.mark()
        assert list(tr.spanned("seam.next", slow())) == [0, 1, 2]
        got = tr.account.since(mark)["seam.next"]
        # three items, the exhausted fourth call left out; the waits inside
        assert got["calls"] == 3 and got["total_s"] >= 0.03

    def test_discarded_span_feeds_nothing(self):
        tr = trace_mod.configure(enabled=True)
        mark = tr.account.mark()
        with tr.span("seam.gone") as sp:
            sp.discard()
        assert "seam.gone" not in tr.account.since(mark)
        assert "seam.gone" not in [r.name for r in tr.records()]

    def test_account_loses_no_update_under_threads(self):
        import sys

        tr = trace_mod.tracer()
        mark = tr.account.mark()
        n_threads, n_each = 16, 400

        def work():
            for _ in range(n_each):
                with tr.span("seam.race", bytes=1):
                    pass

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            ts = [threading.Thread(target=work) for _ in range(n_threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in ts)
        finally:
            sys.setswitchinterval(old)
        got = tr.account.since(mark)["seam.race"]
        assert got["calls"] == got["bytes"] == n_threads * n_each

    def test_capacity_resize_keeps_the_account(self):
        tr = trace_mod.tracer()
        with tr.span("seam.kept"):
            pass
        tr2 = trace_mod.configure(capacity=64)
        assert tr2.account is tr.account
        assert tr2.account.snapshot()["seam.kept"]["calls"] >= 1
        trace_mod.configure(capacity=trace_mod.DEFAULT_CAPACITY)

    def test_fit_log_is_bounded(self):
        for i in range(trace_mod.FIT_LOG_LENGTH + 5):
            trace_mod.record_fit({"path": "t", "steps": i})
        log = trace_mod.fit_log()
        assert len(log) == trace_mod.FIT_LOG_LENGTH
        assert log[-1]["steps"] == trace_mod.FIT_LOG_LENGTH + 4


@pytest.fixture()
def rows120(iris_like):
    """120 rows: batches of 24 or 40 divide over the 8-device data axis,
    so ParallelWrapper pads nothing and `put` bytes are the arrays'."""
    from deeplearning4j_tpu.datasets.dataset import DataSet

    return DataSet(iris_like.features[:120], iris_like.labels[:120])


class TestFitLog:
    @pytest.mark.parametrize("path", sorted(_fit_paths()))
    def test_every_fit_path_accounts_its_phases(self, path, rows120):
        """Gate off: each entry point leaves a fit_log() entry whose leaf
        phases were entered once a step, fit inside the fit's wall time,
        and whose `put` carries the bytes handed over."""
        from deeplearning4j_tpu import telemetry

        tr = trace_mod.tracer()
        assert not tr.enabled
        model = _fit_paths()[path]()
        model.fit(ListDataSetIterator(rows120, batch=24), epochs=2)
        fit = telemetry.fit_log()[-1]
        assert fit["path"] == path and fit["steps"] == 10
        for name in LEAF_PHASES + ("step",):
            assert fit["phases"][name]["calls"] == 10, name
        leaf_s = sum(fit["phases"][n]["total_s"] for n in LEAF_PHASES)
        assert 0 < leaf_s <= fit["wall_s"]
        assert fit["phases"]["step"]["total_s"] <= fit["wall_s"]
        nbytes = rows120.features.nbytes + rows120.labels.nbytes
        assert fit["phases"]["put"]["bytes"] == 2 * nbytes
        assert len(tr) == 0 and tr.dropped == 0   # no SpanRecord allocated

    def test_windowed_fit_dispatches_once_a_window(self, rows120,
                                                   monkeypatch):
        from deeplearning4j_tpu import telemetry
        from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper

        monkeypatch.setenv(WINDOW_GATE, "2")
        pw = ParallelWrapper(_net(), mesh_spec=MeshSpec(data=8))
        pw.fit(ListDataSetIterator(rows120, batch=24), epochs=1)
        assert pw.model._window_scan_cache   # windowing really engaged
        fit = telemetry.fit_log()[-1]
        phases = fit["phases"]
        assert fit["steps"] == 5
        # 5 batches at K=2: windows of 2, 2 and the tail of 1
        assert phases["dispatch"]["calls"] == 3
        assert phases["score_wait"]["calls"] == 3
        assert phases["listeners"]["calls"] == 3
        assert phases["put"]["calls"] == 5 + 3   # each stage + each stack
        assert phases["put"]["bytes"] == (rows120.features.nbytes
                                          + rows120.labels.nbytes)
        assert phases["etl"]["calls"] == 5
        assert len(trace_mod.tracer()) == 0

    def test_compiles_counted_with_the_gate_off(self, rows120):
        from deeplearning4j_tpu import telemetry
        from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper
        from deeplearning4j_tpu.telemetry import introspect

        assert not trace_mod.tracer().enabled
        pw = ParallelWrapper(_net(), mesh_spec=MeshSpec(data=8))
        c0 = introspect.watcher().compile_count()
        pw.fit(ListDataSetIterator(rows120, batch=24), epochs=1)
        c1 = introspect.watcher().compile_count()
        pw.fit(ListDataSetIterator(rows120, batch=24), epochs=1)
        c2 = introspect.watcher().compile_count()
        assert c1 > c0 and c2 == c1
        first, second = telemetry.fit_log()[-2:]
        assert first["compiles"] == c1 - c0 and second["compiles"] == 0

    def test_gate_on_ring_holds_the_phases_under_one_trace(self, rows120,
                                                           monkeypatch):
        from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper

        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        tr = trace_mod.tracer()
        ParallelWrapper(_net(), mesh_spec=MeshSpec(data=8)).fit(
            ListDataSetIterator(rows120, batch=40), epochs=1)
        recs = [r for r in tr.records()
                if r.name in LEAF_PHASES + ("step",)]
        by = {}
        for r in recs:
            by.setdefault(r.name, []).append(r)
        assert {n: len(v) for n, v in by.items()} == {
            n: 3 for n in LEAF_PHASES + ("step",)}
        assert len({r.trace_id for r in recs}) == 1
        assert recs[0].trace_id is not None
        # dispatch, score_wait and listeners are children of their `step`
        # span; the first batch's etl and put precede every step, and
        # those of batch k+1 are children of step k (the look-ahead)
        steps = [r.span_id for r in sorted(by["step"],
                                           key=lambda r: r.start)]
        for name in ("dispatch", "score_wait", "listeners"):
            assert {r.parent_id for r in by[name]} == set(steps)
        for name in ("etl", "put"):
            recs_n = sorted(by[name], key=lambda r: r.start)
            assert recs_n[0].parent_id not in steps
            assert [r.parent_id for r in recs_n[1:]] == steps[:-1], name

    def test_profiler_trace_holds_the_spans_on_its_own_clock(
            self, rows120, tmp_path):
        """Gate off, under jax.profiler: the host plane carries
        dl4j.step events, each enclosing its dispatch, score_wait and
        listeners on the same line and, between dispatch and score_wait,
        the etl and put of the NEXT batch (the first batch's stand before
        the first step); the prefetch thread's dl4j.produce is on
        another line."""
        import glob

        import jax
        from jax.profiler import ProfileData

        from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper

        pw = ParallelWrapper(_net(), mesh_spec=MeshSpec(data=8))
        pw.fit(ListDataSetIterator(rows120, batch=40), epochs=1)  # warm
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            pw.fit(ListDataSetIterator(rows120, batch=40), epochs=1)
        finally:
            jax.profiler.stop_trace()
        assert len(trace_mod.tracer()) == 0
        path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
        lines = []
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    evs = [(e.name.split("#")[0], e.start_ns,
                            e.start_ns + e.duration_ns)
                           for e in line.events
                           if e.name.startswith("dl4j.")]
                    if evs:
                        lines.append(evs)
        fit_line, = [l for l in lines if any(n == "dl4j.step"
                                             for n, _, _ in l)]
        steps = [(s, e) for n, s, e in fit_line if n == "dl4j.step"]
        assert len(steps) == 3
        steps.sort()

        def of(name):
            return sorted((s, e) for n, s, e in fit_line if n == name)

        for name in ("dl4j.dispatch", "dl4j.score_wait", "dl4j.listeners"):
            assert len(of(name)) == 3, name
            for (s, e), (s0, e0) in zip(of(name), steps):
                assert s0 <= s and e <= e0, name
        # 4 etl: the last, inside step 2, only learns the iterator ended
        # (on the profiler's line; the ring and the account leave it out)
        for name, count in (("dl4j.etl", 4), ("dl4j.put", 3)):
            ahead = of(name)
            assert len(ahead) == count, name
            assert ahead[0][1] <= steps[0][0], name   # before step 0
            for k, (s, e) in enumerate(ahead[1:]):    # batch k+1 in step k
                assert of("dl4j.dispatch")[k][1] <= s, name
                assert e <= of("dl4j.score_wait")[k][0], name
        other = [l for l in lines if l is not fit_line]
        assert any(n == "dl4j.produce" for l in other for n, _, _ in l)
