"""Runtime introspection (ISSUE 4): the compile watcher + retrace
detector over the jaxcompat.jit seam, the MFU/roofline engine against a
hand-counted GEMM, HBM sampling as a guarded no-op on CPU, the `profile`
CLI + `/profile` endpoint, the `trace summary` compile/retrace rows, and
the telemetry-disabled zero-allocation contract extended to the
watcher."""
import json
import urllib.request
import warnings

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn import updaters
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.telemetry import introspect, profiler
from deeplearning4j_tpu.telemetry import metrics as metrics_mod
from deeplearning4j_tpu.telemetry import trace as trace_mod
from deeplearning4j_tpu.nn.layers import Dense, Output


def _net(seed=1):
    conf = NeuralNetConfiguration(
        seed=seed, updater=updaters.Adam(learning_rate=5e-3),
    ).list([
        Dense(n_out=16, activation="relu"),
        Output(n_out=3, loss="mcxent"),
    ]).set_input_type(it.feed_forward(4))
    return MultiLayerNetwork(conf).init()


def _batch(rng, b):
    x = rng.normal(size=(b, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, b)]
    return DataSet(x, y)


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("DL4J_TPU_TELEMETRY", raising=False)
    monkeypatch.delenv("DL4J_TPU_PROFILE_LAYERS", raising=False)
    monkeypatch.delenv("DL4J_TPU_RETRACE_THRESHOLD", raising=False)
    trace_mod.configure(enabled=None)
    trace_mod.tracer().clear()
    metrics_mod.registry().reset()
    introspect.reset()
    introspect.configure(layer_every=None)
    yield
    trace_mod.configure(enabled=None)
    trace_mod.tracer().clear()
    metrics_mod.registry().reset()
    introspect.reset()
    introspect.configure(layer_every=None)


# ===========================================================================
# compile watcher / retrace detector
# ===========================================================================


class TestCompileWatcher:
    def test_retrace_detector_fires_on_shape_churn(self, rng, monkeypatch):
        """Deliberate batch-size churn recompiles the train step past the
        threshold: warning metric + chrome instant event + one
        warnings.warn."""
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        net = _net()
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for b in (30, 29, 28, 27, 26, 25):
                net.fit(_batch(rng, b))
        assert any("retraced" in str(w.message) for w in caught)
        snap = metrics_mod.registry().snapshot()
        retraces = snap.get("dl4j_tpu_retrace_warnings_total", {})
        assert retraces.get("fn=MultiLayerNetwork.train_step", 0) >= 1
        instants = [r for r in trace_mod.tracer().records()
                    if r.phase == "i" and r.name == "retrace"]
        assert instants
        assert instants[0].attrs["fn"] == "MultiLayerNetwork.train_step"
        # compile spans carry the fn attribution
        compiles = [r for r in trace_mod.tracer().records()
                    if r.name == "compile"]
        assert len(compiles) == 6  # one per distinct batch shape

    def test_stable_shapes_stay_silent(self, rng, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        net = _net()
        ds = _batch(rng, 30)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for _ in range(6):
                net.fit(ds)
        assert not any("retraced" in str(w.message) for w in caught)
        snap = metrics_mod.registry().snapshot()
        # reset() keeps prior-test label children registered at 0: assert
        # no VALUE, not no key
        assert not any(
            snap.get("dl4j_tpu_retrace_warnings_total", {}).values())
        w = introspect.watcher().snapshot()
        assert w["fns"]["MultiLayerNetwork.train_step"]["traces"] == 1

    def test_disabled_gate_no_records_no_fingerprints(self, rng,
                                                      monkeypatch):
        """ISSUE 4 acceptance: gate unset + retrace-triggering churn ->
        zero span records AND the watcher never fingerprints a call (the
        wrapped step is the raw jitted call behind one check)."""
        monkeypatch.delenv("DL4J_TPU_TELEMETRY", raising=False)
        tr = trace_mod.tracer()
        net = _net()
        for b in (30, 29, 28, 27, 26):
            net.fit(_batch(rng, b))
        assert len(tr) == 0 and tr.dropped == 0
        assert introspect.watcher().snapshot()["fns"] == {}
        snap = metrics_mod.registry().snapshot()
        # children may exist at 0 from earlier tests (reset() keeps
        # registrations); the disabled contract is about VALUES
        assert not any(
            snap.get("dl4j_tpu_retrace_warnings_total", {}).values())
        assert not any(snap.get("dl4j_tpu_compiles_total", {}).values())

    def test_threshold_env_gate(self, rng, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        monkeypatch.setenv("DL4J_TPU_RETRACE_THRESHOLD", "1")
        net = _net()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            net.fit(_batch(rng, 30))
            net.fit(_batch(rng, 29))  # 2nd fingerprint > threshold 1
        snap = metrics_mod.registry().snapshot()
        assert snap["dl4j_tpu_retrace_warnings_total"][
            "fn=MultiLayerNetwork.train_step"] == 1.0


# ===========================================================================
# MFU / roofline engine
# ===========================================================================


class TestMfu:
    def test_cost_analysis_matches_hand_counted_gemm(self):
        """XLA's FLOP count for an m×k · k×n matmul is exactly 2mkn."""
        import jax
        import jax.numpy as jnp

        m, k, n = 64, 32, 16
        f = jax.jit(lambda a, b: a @ b)
        cost = profiler.jit_cost(f, jnp.ones((m, k)), jnp.ones((k, n)))
        assert cost is not None
        assert cost["flops"] == 2 * m * k * n

    def test_mfu_report_math_and_gauges(self, monkeypatch):
        """MFU = flops / (step_s · peak); roofline bound flips with the
        arithmetic-intensity / ridge comparison; gauges published."""
        monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "1e12")
        monkeypatch.setenv("DL4J_TPU_HBM_GBPS", "1000")  # ridge = 1.0
        rep = profiler.mfu_report(flops=5e9, byts=1e9,
                                  step_seconds=0.01)
        assert rep["mfu"] == pytest.approx(5e9 / 0.01 / 1e12)
        assert rep["arithmetic_intensity"] == pytest.approx(5.0)
        assert rep["bound"] == "compute"
        rep2 = profiler.mfu_report(flops=5e8, byts=1e9,
                                   step_seconds=0.01)
        assert rep2["bound"] == "memory"
        snap = metrics_mod.registry().snapshot()
        assert snap["dl4j_tpu_mfu"] == pytest.approx(rep2["mfu"])

    def test_peaks_are_keyed_by_device_kind(self, monkeypatch):
        """An accelerator takes the row of its own `device_kind`; one
        that is not in the table raises instead of borrowing the v5e's
        peaks."""
        import types

        import jax

        def chip(kind):
            return [types.SimpleNamespace(platform="tpu", device_kind=kind)]

        monkeypatch.setattr(jax, "devices", lambda *a: chip("TPU v5 lite"))
        assert profiler.peak_flops() == 197e12
        assert profiler.peak_hbm_bytes_per_s() == 819e9
        monkeypatch.setattr(jax, "devices", lambda *a: chip("TPU v9"))
        with pytest.raises(KeyError, match="TPU v9"):
            profiler.peak_flops()

    def test_step_mfu_falls_back_to_analyzer(self, rng):
        """A net whose step can't be lowered still gets a labeled
        DLA008-estimate MFU."""
        net = _net()
        net._train_step = object()  # no .lower -> cost_analysis path dies
        ds = _batch(rng, 8)
        rep = profiler.step_mfu(net, ds.features, ds.labels,
                                step_seconds=0.01)
        assert rep is not None
        assert rep["source"] == "analyzer(DLA008)"
        est = {"flops": 6 * net.num_params() * 8}
        assert rep["flops_per_step"] == est["flops"]


# ===========================================================================
# HBM sampling (CPU = guarded no-op)
# ===========================================================================


class TestHbmSampler:
    def test_cpu_sampling_is_noop(self, rng, monkeypatch):
        """On CPU: no exception, no dl4j_tpu_hbm_* series, and the fit
        hook resolves to the NULL singleton."""
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        assert introspect.hbm_stats() == {}
        assert introspect.sample_hbm() == {}
        net = _net()
        fi = introspect.fit_introspection(net)
        assert fi is introspect.NULL_FIT
        net.fit(_batch(rng, 16))
        text = metrics_mod.render_prometheus()
        assert "dl4j_tpu_hbm_bytes" not in text
        assert "dl4j_tpu_hbm_peak_bytes" not in text

    def test_predicted_bytes_comes_from_analyzer(self, rng):
        net = _net()
        net.fit(_batch(rng, 16))
        from deeplearning4j_tpu.analysis import estimate_costs

        est = estimate_costs(net.conf, batch=16)
        assert introspect.predicted_train_bytes(net) == est["train_bytes"]


# ===========================================================================
# sampled per-layer spans
# ===========================================================================


class TestLayerSpans:
    def test_sampled_lanes_and_top_layers(self, rng, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        introspect.configure(layer_every=2)
        net = _net()
        net.fit(ListDataSetIterator(_batch(rng, 60), batch=20), epochs=1)
        layer_spans = [r for r in trace_mod.tracer().records()
                       if r.category == "layer"]
        assert layer_spans  # iterations 1..3 -> iteration 2 sampled
        # fwd spans for both layers, on the dedicated lane
        names = {r.name for r in layer_spans}
        assert {"layer_0.fwd", "layer_1.fwd"} <= names
        assert {r.thread_id for r in layer_spans} == {998}
        doc = trace_mod.tracer().to_chrome_trace()
        lanes = {e["args"]["name"] for e in doc["traceEvents"]
                 if e["ph"] == "M"}
        assert "layer profile" in lanes
        top = introspect.top_layers()
        assert top and top[0]["total_ms"] >= top[-1]["total_ms"]

    def test_off_by_default(self, rng, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        net = _net()
        net.fit(_batch(rng, 16))
        assert not [r for r in trace_mod.tracer().records()
                    if r.category == "layer"]


# ===========================================================================
# surfacing: profile CLI, /profile endpoint, trace summary rows
# ===========================================================================


class TestSurfacing:
    def test_profile_cli_smoke(self, capsys):
        from deeplearning4j_tpu.cli import main

        rc = main(["profile", "--model", "lenet", "--iters", "2",
                   "--batch", "4", "--layer-every", "1"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "step p50" in out
        assert "estimated MFU" in out
        assert "compile count" in out
        assert "unavailable" in out  # the CPU HBM section
        assert "top layers" in out
        # and the run restored the env gate (no leak into later fits)
        assert not trace_mod.tracer().enabled

    def test_profile_cli_json(self, capsys):
        from deeplearning4j_tpu.cli import main

        rc = main(["profile", "--model", "lenet", "--iters", "2",
                   "--batch", "4", "--json"])
        assert rc == 0
        rep = json.loads(capsys.readouterr().out)
        assert rep["step_count"] == 2
        assert rep["hbm"] == "unavailable"
        assert rep["compile_count"] >= 1
        assert rep["mfu"]["mfu"] > 0

    def test_profile_endpoint(self, rng, monkeypatch):
        from deeplearning4j_tpu.ui.server import UIServer

        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        net = _net()
        net.fit(_batch(rng, 16))
        server = UIServer(port=0)
        try:
            with urllib.request.urlopen(server.url() + "/profile") as r:
                assert r.status == 200
                doc = json.loads(r.read())
        finally:
            server.stop()
        assert doc["enabled"] is True
        assert "step" in doc["phases"]
        assert doc["hbm"] == "unavailable"
        assert "MultiLayerNetwork.train_step" in doc["compile"]["fns"]

    def test_trace_summary_reports_compile_and_retraces(self, rng,
                                                        tmp_path,
                                                        monkeypatch,
                                                        capsys):
        """One command answers 'why was this run slow': the summary
        table grows compile totals and retrace warnings when the trace
        carries them."""
        from deeplearning4j_tpu.cli import main

        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        net = _net()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            for b in (30, 29, 28, 27, 26):
                net.fit(_batch(rng, b))
        path = str(tmp_path / "trace.json")
        trace_mod.tracer().export_chrome(path)
        assert main(["trace", "summary", "--file", path]) == 0
        out = capsys.readouterr().out
        assert "compile:" in out
        assert "retrace warning:" in out
        assert "MultiLayerNetwork.train_step" in out
        # machine mode carries the same facts
        assert main(["trace", "summary", "--file", path, "--json"]) == 0
        parsed = json.loads(capsys.readouterr().out)
        assert parsed["_introspection"]["compile_count"] == 5
        assert parsed["_introspection"]["retraces"][
            "MultiLayerNetwork.train_step"] >= 1
