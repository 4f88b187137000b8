"""Overload-hardened serving runtime (ISSUE 8 acceptance): continuous
batching into warmed buckets (retrace-silent steady state), deadline
admission + in-queue expiry, bounded queue with both shed policies,
the breaker's exact open -> half_open -> closed arc under chaos (with a
breaker-open flight bundle), drain-on-shutdown / dispatcher-crash
surfacing (no caller EVER blocks forever), the sustained-load chaos
matrix over N client threads, /healthz breaker surfacing (503 while
open), the fixed legacy ParallelInference dispatcher, and the gate-off
zero-allocation contract."""
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.parallel import MeshSpec, ParallelInference, build_mesh
from deeplearning4j_tpu.resilience import chaos
from deeplearning4j_tpu.serving import (
    BucketSpec,
    CircuitBreaker,
    CircuitOpenError,
    DeadlineExceededError,
    DispatchFailedError,
    DispatcherCrashedError,
    NonFiniteOutputError,
    ServingError,
    ShedError,
    ShutdownError,
)
from deeplearning4j_tpu.serving import buckets as buckets_mod
from deeplearning4j_tpu.serving.runtime import InferenceServer, healthz_section
from deeplearning4j_tpu.telemetry import metrics as metrics_mod
from deeplearning4j_tpu.telemetry import trace as trace_mod


@pytest.fixture(autouse=True)
def _clean(monkeypatch, tmp_path):
    monkeypatch.setenv("DL4J_TPU_FLIGHT_DIR", str(tmp_path / "flight"))
    trace_mod.configure(enabled=None)
    metrics_mod.registry().reset()
    chaos.reset_fault_points()
    yield
    trace_mod.configure(enabled=None)
    metrics_mod.registry().reset()
    chaos.reset_fault_points()


def _counter(name):
    m = metrics_mod.registry().get(name)
    return {} if m is None else m.snapshot()


def _double(x):
    return x * 2.0


def _server(**kw):
    kw.setdefault("dispatch", _double)
    kw.setdefault("batch_limit", 8)
    kw.setdefault("queue_limit", 16)
    kw.setdefault("wait_ms", 1.0)
    kw.setdefault("name", "test")
    return InferenceServer(**kw)


def _serving_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("InferenceServer-dispatch") and
            t.is_alive()]


class _FakeModel:
    """model.output contract only — what both dispatchers actually need."""

    def __init__(self, fn=None, delay=0.0):
        self.fn = fn or (lambda x: np.asarray(x) * 2.0)
        self.delay = delay

    def output(self, x):
        if self.delay:
            time.sleep(self.delay)
        return self.fn(np.asarray(x))


# ===========================================================================
# buckets
# ===========================================================================


class TestBuckets:
    def test_power_of_two_aligned_sizes(self):
        spec = BucketSpec(32, align=8)
        assert spec.sizes == (8, 16, 32)
        assert spec.bucket_for(1) == 8
        assert spec.bucket_for(9) == 16
        assert spec.bucket_for(32) == 32
        assert spec.bucket_for(33) is None
        # oversize dispatches alone at the next align multiple
        assert spec.padded_size(33) == 40

    def test_explicit_sizes_rounded_and_sorted(self):
        spec = BucketSpec(64, align=4, sizes=(30, 7, 7))
        assert spec.sizes == (8, 32)

    def test_pad_rows_repeats_last(self):
        x = np.arange(6, dtype=np.float32).reshape(3, 2)
        p = buckets_mod.pad_rows(x, 5)
        assert p.shape == (5, 2)
        np.testing.assert_array_equal(p[3], x[-1])
        assert buckets_mod.pad_rows(x, 3) is x
        with pytest.raises(ValueError):
            buckets_mod.pad_rows(x, 2)

    def test_signature_keys_trailing_shape_and_dtype(self):
        a = np.zeros((2, 4), np.float32)
        b = np.zeros((9, 4), np.float32)
        c = np.zeros((2, 5), np.float32)
        d = np.zeros((2, 4), np.float64)
        assert buckets_mod.signature(a) == buckets_mod.signature(b)
        assert buckets_mod.signature(a) != buckets_mod.signature(c)
        assert buckets_mod.signature(a) != buckets_mod.signature(d)


# ===========================================================================
# circuit breaker
# ===========================================================================


class TestBreaker:
    def test_arc_with_exact_transitions(self):
        br = CircuitBreaker(failure_threshold=2, cooldown_s=0.05,
                            probe_successes=2)
        assert br.allow_request() and br.state == "closed"
        br.record_failure("a")
        assert br.state == "closed"  # streak 1 < threshold
        assert br.record_failure("b") is True  # this one opened it
        assert br.state == "open"
        assert not br.allow_request()
        assert 0.0 < br.retry_after_s() <= 0.05
        time.sleep(0.06)
        assert br.allow_request()  # cooldown elapsed -> probe admitted
        assert br.state == "half_open"
        assert not br.allow_request()  # max_probes=1: one at a time
        br.record_success()
        assert br.state == "half_open"  # streak 1 < probe_successes
        assert br.allow_request()
        br.record_success()
        assert br.state == "closed"
        snap = _counter("dl4j_tpu_serving_breaker_transitions_total")
        assert snap == {"state=closed": 1.0, "state=half_open": 1.0,
                        "state=open": 1.0}

    def test_half_open_failure_reopens(self):
        br = CircuitBreaker(failure_threshold=1, cooldown_s=0.03,
                            probe_successes=1)
        br.record_failure("x")
        time.sleep(0.04)
        assert br.allow_request()
        assert br.record_failure("probe failed") is True
        assert br.state == "open"
        assert br.retry_after_s() > 0.0  # fresh cooldown

    def test_success_resets_streak(self):
        br = CircuitBreaker(failure_threshold=2)
        br.record_failure("a")
        br.record_success()
        br.record_failure("b")
        assert br.state == "closed"  # never two CONSECUTIVE failures

    def test_release_probe_returns_slot(self):
        br = CircuitBreaker(failure_threshold=1, cooldown_s=0.0)
        br.record_failure("x")
        assert br.allow_request()  # takes the half-open probe slot
        assert not br.allow_request()
        br.release_probe()  # admission refused the request elsewhere
        assert br.allow_request()

    def test_probe_expiring_in_queue_does_not_wedge_breaker(self):
        """A half-open probe resolved WITHOUT a dispatch result (its
        deadline expired in the queue behind a slow pre-open dispatch)
        must repay its slot — otherwise the breaker sits in HALF_OPEN
        rejecting 100% of traffic forever, even after recovery."""
        br = CircuitBreaker(failure_threshold=1, cooldown_s=0.01,
                            probe_successes=1)

        def dispatch(x):
            v = x[0, 0]
            if v == 1:
                raise RuntimeError("boom")
            if v == 2:
                time.sleep(0.25)
            return x * 2.0

        s = _server(dispatch=dispatch, batch_limit=1, wait_ms=0.0,
                    breaker=br)
        fast = np.zeros((1, 2), np.float32)
        try:
            s.output(fast)  # prime a TINY ema: admission will underrate
            h = s.submit(np.full((1, 2), 2, np.float32))  # slow, 0.25s
            b = s.submit(np.full((1, 2), 1, np.float32))  # opens breaker
            d = s.submit(np.full((1, 2), 2, np.float32))  # slow, 0.25s
            with pytest.raises(DispatchFailedError):
                s.result(b)
            assert br.state == "open"
            time.sleep(0.02)  # cooldown (0.01s) elapses; d's 0.25s
            # dispatch is in flight — the probe will sit QUEUED behind
            # it past its whole deadline
            probe = s.submit(fast, deadline_s=0.1)
            assert probe.probe  # holds THE half-open slot
            s.result(h)
            s.result(d)
            # the dispatcher's expired-head sweep resolved the probe
            # without any record_success/record_failure — its slot must
            # have been released, not leaked
            limit = time.perf_counter() + 2.0
            while not probe.event.is_set():
                assert time.perf_counter() < limit
                time.sleep(0.01)
            assert isinstance(probe.error, DeadlineExceededError)
            # the regression: a NEW probe is admitted and closes the
            # breaker (a leaked slot would CircuitOpenError here forever)
            np.testing.assert_array_equal(
                s.output(fast, deadline_s=2.0), fast)
            assert br.state == "closed"
        finally:
            s.shutdown()

    def test_probe_drained_at_shutdown_releases_slot(self):
        br = CircuitBreaker(failure_threshold=1, cooldown_s=0.0,
                            probe_successes=1)
        br.record_failure("x")
        allowed, probe = br.admit()
        assert allowed and probe
        # the runtime's no-dispatch resolution paths release it
        br.release_probe()
        assert br.allow_request()  # not wedged


# ===========================================================================
# serving runtime
# ===========================================================================


class TestInferenceServer:
    def test_concurrent_roundtrip_and_latency_metrics(self):
        s = _server()
        try:
            import concurrent.futures as cf

            xs = [np.full((2, 4), i, np.float32) for i in range(24)]
            with cf.ThreadPoolExecutor(8) as ex:
                outs = list(ex.map(s.output, xs))
            for o, x in zip(outs, xs):
                np.testing.assert_array_equal(o, x * 2.0)
            assert _counter("dl4j_tpu_serving_requests_total")[
                "outcome=ok"] == 24.0
            hist = _counter("dl4j_tpu_serving_latency_seconds")
            assert hist["count"] == 24
            snap = s.snapshot()
            assert snap["latency_p50_s"] is not None
            assert snap["latency_p99_s"] >= snap["latency_p50_s"]
        finally:
            s.shutdown()

    def test_coalesces_but_never_overshoots_batch_limit(self):
        rows = []

        def record(x):
            rows.append(x.shape[0])
            time.sleep(0.01)  # hold the dispatcher so a backlog forms
            return x

        s = _server(dispatch=record, batch_limit=4, wait_ms=5.0,
                    buckets=BucketSpec(4, sizes=(4,)))
        try:
            reqs = [s.submit(np.zeros((1, 3), np.float32))
                    for _ in range(10)]
            for r in reqs:
                s.result(r)
            # backlogged singles coalesced into padded bucket dispatches;
            # every dispatch is exactly the 4-row bucket (padded), and
            # there were FEWER dispatches than requests
            assert set(rows) == {4}
            assert len(rows) < 10
        finally:
            s.shutdown()

    def test_oversize_request_dispatches_alone(self):
        rows = []

        def record(x):
            rows.append(x.shape[0])
            return x

        s = _server(dispatch=record, batch_limit=8)
        try:
            x = np.arange(60, dtype=np.float32).reshape(20, 3)
            out = s.output(x)
            np.testing.assert_array_equal(out, x)
            assert 20 in rows  # alone, not silently merged past the limit
        finally:
            s.shutdown()

    def test_mismatched_signature_fails_alone(self):
        def picky(x):
            if x.shape[1] != 4:
                raise ValueError("bad trailing shape")
            return x

        s = _server(dispatch=picky, wait_ms=5.0)
        try:
            good = np.zeros((2, 4), np.float32)
            bad = np.zeros((2, 5), np.float32)
            reqs = [s.submit(good), s.submit(bad), s.submit(good)]
            np.testing.assert_array_equal(s.result(reqs[0]), good)
            np.testing.assert_array_equal(s.result(reqs[2]), good)
            with pytest.raises(DispatchFailedError):
                s.result(reqs[1])
        finally:
            s.shutdown()

    def test_deadline_admission_reject_and_queue_expiry(self):
        def dispatch(x):
            if x[0, 0] == 99:  # the one deliberately-slow request
                time.sleep(0.25)
            return x * 2.0

        s = _server(dispatch=dispatch, batch_limit=1, wait_ms=0.0,
                    queue_limit=16)
        try:
            s.output(np.zeros((1, 2), np.float32))  # prime a SMALL EMA
            blocker = s.submit(np.full((1, 2), 99, np.float32))
            time.sleep(0.02)  # blocker enters flight for 0.25s
            # admitted (tiny EMA says 0.1s is plenty) but expires in the
            # queue behind the slow dispatch — typed error AT the
            # deadline, not after the blocker finishes
            t0 = time.perf_counter()
            victim = s.submit(np.zeros((1, 2), np.float32),
                              deadline_s=0.1)
            with pytest.raises(DeadlineExceededError):
                s.result(victim)
            assert time.perf_counter() - t0 < 0.2
            s.result(blocker)
            # the 0.25s dispatch raised the EMA: a deadline below the
            # estimate is now refused at ADMISSION, instantly
            t0 = time.perf_counter()
            with pytest.raises(DeadlineExceededError):
                s.output(np.zeros((1, 2), np.float32), deadline_s=0.005)
            assert time.perf_counter() - t0 < 0.05
            time.sleep(0.05)  # the dispatcher logs the queue expiry too
            shed = _counter("dl4j_tpu_serving_shed_total")
            assert shed["reason=deadline"] >= 2.0
        finally:
            s.shutdown()

    def test_shed_reject_newest_with_retry_after(self):
        s = _server(dispatch=lambda x: (time.sleep(0.1), x * 2.0)[1],
                    batch_limit=1, wait_ms=0.0, queue_limit=2,
                    shed_policy="reject_newest")
        try:
            s.output(np.zeros((1, 2), np.float32))  # prime the EMA
            held = [s.submit(np.zeros((1, 2), np.float32))]
            time.sleep(0.02)  # enters flight; now fill the queue
            held += [s.submit(np.zeros((1, 2), np.float32))
                     for _ in range(2)]
            with pytest.raises(ShedError) as ei:
                for _ in range(4):
                    s.submit(np.zeros((1, 2), np.float32))
            assert ei.value.retry_after_s > 0.0
            assert _counter("dl4j_tpu_serving_shed_total")[
                "reason=queue_full"] >= 1.0
            for r in held:
                s.result(r)
        finally:
            s.shutdown()

    def test_shed_drop_oldest_resolves_the_dropped(self):
        s = _server(dispatch=lambda x: (time.sleep(0.1), x * 2.0)[1],
                    batch_limit=1, wait_ms=0.0, queue_limit=1,
                    shed_policy="drop_oldest")
        try:
            blocker = s.submit(np.zeros((1, 2), np.float32))
            time.sleep(0.02)  # blocker enters flight; queue is empty
            oldest = s.submit(np.full((1, 2), 1, np.float32))  # fills it
            newest = s.submit(np.full((1, 2), 2, np.float32))  # overflow
            # the policy dropped the OLDEST queued request, with a typed
            # error, to make room for the newest
            with pytest.raises(ShedError) as ei:
                s.result(oldest)
            assert ei.value.retry_after_s is not None
            np.testing.assert_array_equal(
                s.result(newest), np.full((1, 2), 4.0, np.float32))
            s.result(blocker)
            assert _counter("dl4j_tpu_serving_shed_total")[
                "reason=drop_oldest"] == 1.0
        finally:
            s.shutdown()

    def test_breaker_arc_under_chaos_with_flight_bundle(self, monkeypatch,
                                                        tmp_path):
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        trace_mod.configure(enabled=True)
        monkeypatch.setenv("DL4J_TPU_CHAOS", "serving_dispatch@1:2")
        chaos.reset_fault_points()
        br = CircuitBreaker(failure_threshold=2, cooldown_s=0.08,
                            probe_successes=2)
        s = _server(breaker=br, batch_limit=1, wait_ms=0.0)
        try:
            x = np.zeros((1, 2), np.float32)
            for _ in range(2):
                with pytest.raises(DispatchFailedError):
                    s.output(x)
            assert br.state == "open"
            with pytest.raises(CircuitOpenError) as ei:
                s.output(x)
            assert ei.value.retry_after_s > 0.0
            time.sleep(0.1)
            s.output(x)  # half-open probe 1
            assert br.state == "half_open"
            s.output(x)  # probe 2 closes
            assert br.state == "closed"
            assert _counter(
                "dl4j_tpu_serving_breaker_transitions_total") == {
                    "state=closed": 1.0, "state=half_open": 1.0,
                    "state=open": 1.0}
            assert _counter("dl4j_tpu_serving_shed_total")[
                "reason=breaker_open"] == 1.0
            # opening wrote ONE flight bundle with the breaker reason
            bundles = [f for f in os.listdir(tmp_path / "flight")
                       if "serving_breaker" in f]
            assert len(bundles) == 1
            with open(tmp_path / "flight" / bundles[0]) as fh:
                assert json.load(fh)["reason"] == "serving_breaker"
        finally:
            s.shutdown()

    def test_nan_outputs_trip_breaker(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_CHAOS", "serving_nan@1")
        chaos.reset_fault_points()
        br = CircuitBreaker(failure_threshold=1, cooldown_s=0.03,
                            probe_successes=1)
        s = _server(breaker=br, batch_limit=1, wait_ms=0.0)
        try:
            x = np.zeros((1, 2), np.float32)
            with pytest.raises(NonFiniteOutputError):
                s.output(x)
            assert br.state == "open"
            assert _counter("dl4j_tpu_serving_requests_total")[
                "outcome=nonfinite"] == 1.0
            time.sleep(0.05)
            np.testing.assert_array_equal(s.output(x), x * 2.0)
            assert br.state == "closed"
        finally:
            s.shutdown()

    def test_slow_fault_expires_deadline_not_caller(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_CHAOS", "serving_slow@1")
        chaos.reset_fault_points()
        s = _server(batch_limit=1, wait_ms=0.0, slow_fault_s=0.4)
        try:
            t0 = time.perf_counter()
            with pytest.raises(DeadlineExceededError):
                s.output(np.zeros((1, 2), np.float32), deadline_s=0.05)
            # the caller came back at its deadline, NOT after the 0.4s
            # injected stall
            assert time.perf_counter() - t0 < 0.3
            # the runtime itself recovered
            np.testing.assert_array_equal(
                s.output(np.ones((1, 2), np.float32)),
                np.full((1, 2), 2.0, np.float32))
        finally:
            s.shutdown()

    def test_shutdown_drains_every_queued_request(self):
        s = _server(dispatch=lambda x: (time.sleep(0.1), x)[1],
                    batch_limit=1, wait_ms=0.0)
        reqs = [s.submit(np.zeros((1, 2), np.float32)) for _ in range(5)]
        time.sleep(0.02)  # first enters flight
        t0 = time.perf_counter()
        s.shutdown()
        assert time.perf_counter() - t0 < 2.0  # one dispatch, not five
        outcomes = []
        for r in reqs:
            try:
                s.result(r)
                outcomes.append("ok")
            except ShutdownError:
                outcomes.append("shutdown")
        assert outcomes[0] == "ok"  # in-flight work completed
        assert outcomes[1:] == ["shutdown"] * 4  # queued work drained
        with pytest.raises(ShutdownError):
            s.output(np.zeros((1, 2), np.float32))
        assert not s._thread.is_alive()

    def test_dispatcher_crash_surfaces_to_callers(self):
        def bomb(x):
            raise SystemExit("dispatcher bug")  # escapes Exception handling

        s = _server(dispatch=bomb, batch_limit=1, wait_ms=0.0)
        with pytest.raises(DispatcherCrashedError):
            s.output(np.zeros((1, 2), np.float32))
        # subsequent submits refuse immediately instead of queueing
        with pytest.raises(DispatcherCrashedError):
            s.output(np.zeros((1, 2), np.float32))
        assert _counter("dl4j_tpu_serving_requests_total").get(
            "outcome=crashed", 0.0) >= 1.0
        s.shutdown()

    def test_warmed_buckets_keep_steady_state_retrace_silent(
            self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        trace_mod.configure(enabled=True)
        import jax.numpy as jnp

        from deeplearning4j_tpu.util import jaxcompat

        fwd = jaxcompat.jit(lambda x: x * 3.0,
                            watch_name="serving.test_steady")
        s = _server(dispatch=lambda x: np.asarray(fwd(jnp.asarray(x))),
                    batch_limit=8, buckets=BucketSpec(8, sizes=(4, 8)),
                    wait_ms=0.0)
        try:
            s.warmup(np.zeros((1, 3), np.float32))
            assert len(s.warmed_rows) == 2
            for n in (1, 2, 3, 4, 5, 8, 2, 7):  # varied traffic
                out = s.output(np.ones((n, 3), np.float32))
                assert out.shape == (n, 3)
            # every dispatched shape was pre-warmed: no fresh executable,
            # no retrace warning, in steady state
            assert s.dispatched_rows <= s.warmed_rows
            # zero warnings THIS test (earlier suites' zeroed children
            # may survive the registry reset — values, not keys, matter)
            m = metrics_mod.registry().get(
                "dl4j_tpu_retrace_warnings_total")
            assert m is None or all(v == 0 for v in m.snapshot().values())
        finally:
            s.shutdown()

    def test_healthz_endpoint_503_while_breaker_open(self, monkeypatch):
        from deeplearning4j_tpu.telemetry import health as health_mod
        from deeplearning4j_tpu.ui.server import UIServer

        ui = UIServer(port=0)

        def get(path):
            try:
                r = urllib.request.urlopen(ui.url() + path, timeout=5)
                return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        br = CircuitBreaker(failure_threshold=1, cooldown_s=30.0)
        s = _server(breaker=br)
        try:
            code, body = get("/healthz")
            assert code == 200  # live healthy serving runtime = liveness
            assert body["serving"]["breaker_open"] is False
            assert healthz_section()["queue_depth"] == 0
            br.record_failure("test")
            code, body = get("/healthz")
            assert code == 503
            assert body["reason"] == "serving circuit breaker open"
            assert body["serving"]["breaker_open"] is True
            # a healthy serving side must NOT mask a real training
            # failure: only the never-trained payload flips to 200
            br2 = CircuitBreaker(failure_threshold=1)
            s.breaker = br2  # close the serving side again
            monkeypatch.setattr(
                health_mod, "healthz",
                lambda: {"ok": False, "reason": "stalled", "stalled": 1})
            code, body = get("/healthz")
            assert code == 503
            assert body["reason"] == "stalled"
            assert body["serving"]["breaker_open"] is False
        finally:
            s.shutdown()
            ui.stop()
        # a stopped server no longer reports
        assert healthz_section() is None


# ===========================================================================
# sustained-load chaos matrix (the ISSUE 8 acceptance arc)
# ===========================================================================


class TestChaosMatrix:
    def test_sustained_load_every_request_resolves_in_deadline(
            self, monkeypatch):
        """6 client threads x 20 requests against injected dispatch
        faults (consecutive -> breaker opens), a slow dispatch, NaN
        outputs, and a queue far smaller than the offered load: every
        single call must resolve within its deadline with a result or a
        typed ServingError — zero hung callers — and the breaker must
        complete exactly one open -> half_open -> closed recovery."""
        monkeypatch.setenv(
            "DL4J_TPU_CHAOS", "serving_dispatch@3:4,serving_slow@8,"
                              "serving_nan@12")
        chaos.reset_fault_points()
        br = CircuitBreaker(failure_threshold=2, cooldown_s=0.05,
                            probe_successes=2)
        s = _server(dispatch=lambda x: (time.sleep(0.002), x * 2.0)[1],
                    batch_limit=4, queue_limit=4, wait_ms=0.5,
                    breaker=br, slow_fault_s=0.15)
        n_threads, per_thread = 6, 20
        deadline_s = 2.0
        outcomes = []
        elapsed = []
        lock = threading.Lock()

        def client(k):
            for i in range(per_thread):
                x = np.full((1, 3), k * 100 + i, np.float32)
                t0 = time.perf_counter()
                try:
                    out = s.output(x, deadline_s=deadline_s)
                    np.testing.assert_array_equal(out, x * 2.0)
                    verdict = "ok"
                except ServingError as e:
                    verdict = type(e).__name__
                dt = time.perf_counter() - t0
                with lock:
                    outcomes.append(verdict)
                    elapsed.append(dt)
                # shed/broken-circuit rejections back off briefly (the
                # retry-after contract) so the client fleet is still
                # submitting when the breaker's cooldown elapses —
                # otherwise 6 threads burn all 120 calls inside the
                # 50 ms open window and nobody probes it closed
                time.sleep(0.01 if verdict != "ok" else 0.001)

        threads = [threading.Thread(target=client, args=(k,), daemon=True)
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
        try:
            # zero hung callers: every thread finished, every call
            # resolved within its deadline (+ one wait slice of slack)
            assert not any(t.is_alive() for t in threads)
            assert len(outcomes) == n_threads * per_thread
            assert max(elapsed) < deadline_s + 0.5
            counts = {v: outcomes.count(v) for v in set(outcomes)}
            # the matrix exercised every arc: successes, typed dispatch
            # failures, and at least one shed/breaker/nan outcome
            assert counts.get("ok", 0) > 0
            assert counts.get("DispatchFailedError", 0) > 0
            allowed = {"ok", "DispatchFailedError", "ShedError",
                       "CircuitOpenError", "NonFiniteOutputError",
                       "DeadlineExceededError"}
            assert set(counts) <= allowed
            # exact breaker recovery arc: the two consecutive chaos
            # faults opened it ONCE; probes closed it; the isolated NaN
            # failure later never re-opened (streak 1 < threshold 2)
            assert br.state == "closed"
            assert _counter(
                "dl4j_tpu_serving_breaker_transitions_total") == {
                    "state=closed": 1.0, "state=half_open": 1.0,
                    "state=open": 1.0}
            inj = _counter("dl4j_tpu_chaos_injections_total")
            assert inj.get("point=serving_dispatch") == 2.0
            assert inj.get("point=serving_nan.silent") == 1.0
            assert inj.get("point=serving_slow.silent") == 1.0
        finally:
            s.shutdown()
        assert not s._thread.is_alive()
        assert _serving_threads() == []


# ===========================================================================
# correlated tracing (ISSUE 10 acceptance): one trace_id per request,
# across the caller and dispatcher threads
# ===========================================================================


class TestCorrelatedTracing:
    def _events_for(self, trace_id):
        evs = trace_mod.tracer().to_chrome_trace()["traceEvents"]
        return [e for e in evs
                if (e.get("args") or {}).get("trace_id") == trace_id]

    def test_request_spans_share_one_trace_across_threads(self, monkeypatch):
        """ISSUE 10 acceptance (chaos run): under an injected
        `serving_slow` stall the request still produces ONE trace whose
        admission -> dispatch -> resolve spans share a trace_id, with
        the admission/resolve spans on the caller thread and the
        dispatch span on the dispatcher lane, bound by a flow
        start/finish pair whose flow id IS the trace id."""
        monkeypatch.setenv("DL4J_TPU_CHAOS", "serving_slow@1")
        chaos.reset_fault_points()
        trace_mod.configure(enabled=True)
        s = _server(batch_limit=1, wait_ms=0.0, slow_fault_s=0.05)
        try:
            req = s.submit(np.ones((2, 3), np.float32))
            np.testing.assert_array_equal(
                s.result(req), np.full((2, 3), 2.0, np.float32))
        finally:
            s.shutdown()
        assert req.ctx is not None
        tid = req.ctx.trace_id
        mine = self._events_for(tid)
        names = {e["name"] for e in mine}
        assert {"serving.admission", "serving.dispatch",
                "serving.resolve"} <= names
        # caller thread and dispatcher lane are DIFFERENT tids in the
        # export — the trace id is what joins them
        span_tids = {e["tid"] for e in mine if e["ph"] == "X"}
        assert len(span_tids) >= 2
        # every span in the trace parents transitively to the root
        # (root ctx: span_id == trace_id)
        ids = {e["args"]["span_id"] for e in mine} | {tid}
        assert all(e["args"].get("parent_id") in ids
                   for e in mine if e["args"].get("parent_id"))
        # the flow arrow: start on the caller lane at enqueue, finish on
        # the dispatcher lane at dispatch, bound by flow id == trace id
        flows = [e for e in mine if e["name"] == "serving.batch"]
        assert {e["ph"] for e in flows} == {"s", "f"}
        assert all(e["id"] == tid for e in flows)
        # the per-request dispatch span carries batch geometry + outcome
        disp = next(e for e in mine if e["name"] == "serving.dispatch")
        assert disp["args"]["outcome"] == "ok"
        assert disp["args"]["rows"] == 2
        # the dispatcher lane is named for Perfetto
        doc = trace_mod.tracer().to_chrome_trace()
        lanes = [e["args"]["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "M" and e.get("name") == "thread_name"]
        assert "serving-dispatch-test" in lanes

    def test_batch_flow_links_resolve_to_members(self):
        """A coalesced batch's shared `serving.dispatch_batch` span lists
        every member trace id, and each member gets its OWN dispatch
        span + flow finish on the dispatcher lane."""
        trace_mod.configure(enabled=True)
        gate = threading.Event()
        s = _server(dispatch=lambda x: (gate.wait(2.0), x * 2.0)[1],
                    batch_limit=8, wait_ms=0.0)
        try:
            r1 = s.submit(np.zeros((1, 3), np.float32))
            time.sleep(0.03)  # r1 enters flight and parks on the gate
            r2 = s.submit(np.ones((2, 3), np.float32))
            r3 = s.submit(np.ones((3, 3), np.float32))
            time.sleep(0.03)  # r2+r3 queued; coalesce on next wakeup
            gate.set()
            for r in (r1, r2, r3):
                s.result(r)
        finally:
            s.shutdown()
        evs = trace_mod.tracer().to_chrome_trace()["traceEvents"]
        batches = [e for e in evs
                   if e["name"] == "serving.dispatch_batch"
                   and len(e["args"].get("member_traces", [])) >= 2]
        assert batches, "no coalesced batch span recorded"
        members = batches[0]["args"]["member_traces"]
        assert {r2.ctx.trace_id, r3.ctx.trace_id} <= set(members)
        for ctx_tid in members:
            mine = self._events_for(ctx_tid)
            assert any(e["name"] == "serving.dispatch" for e in mine)
            finishes = [e for e in mine if e["name"] == "serving.batch"
                        and e["ph"] == "f"]
            assert finishes and finishes[0]["id"] == ctx_tid

    def test_shed_request_trace_shows_admission_rejection(self):
        """A shed request's trace ends at admission: its one span is
        `serving.admission` carrying the rejection reason."""
        trace_mod.configure(enabled=True)
        gate = threading.Event()
        s = _server(dispatch=lambda x: (gate.wait(2.0), x * 2.0)[1],
                    batch_limit=1, wait_ms=0.0, queue_limit=1,
                    shed_policy="reject_newest")
        held = []
        try:
            held.append(s.submit(np.zeros((1, 2), np.float32)))
            time.sleep(0.03)  # enters flight; now fill the queue
            held.append(s.submit(np.zeros((1, 2), np.float32)))
            with pytest.raises(ShedError):
                for _ in range(4):
                    held.append(s.submit(np.zeros((1, 2), np.float32)))
            gate.set()
            for r in held:
                s.result(r)
        finally:
            gate.set()
            s.shutdown()
        rejected = [e for e in
                    trace_mod.tracer().to_chrome_trace()["traceEvents"]
                    if e["name"] == "serving.admission"
                    and e.get("args", {}).get("rejected") == "queue_full"]
        assert rejected
        shed_tid = rejected[0]["args"]["trace_id"]
        # the shed trace has NO dispatch/resolve spans — it died at
        # admission, and the trace says exactly that
        names = {e["name"] for e in self._events_for(shed_tid)}
        assert "serving.dispatch" not in names
        assert "serving.resolve" not in names

    def test_gate_off_mints_no_contexts(self):
        before = len(trace_mod.tracer().to_chrome_trace()["traceEvents"])
        s = _server()
        try:
            req = s.submit(np.ones((1, 2), np.float32))
            s.result(req)
        finally:
            s.shutdown()
        assert req.ctx is None  # no TraceContext allocated off-gate
        after = len(trace_mod.tracer().to_chrome_trace()["traceEvents"])
        assert after == before  # and no span records either


# ===========================================================================
# legacy ParallelInference (gate off) — the fixed dispatcher
# ===========================================================================


def _mesh1():
    import jax

    return build_mesh(MeshSpec.data_parallel(1),
                      devices=jax.devices()[:1])


class TestParallelInferenceFixed:
    def _pi(self, model=None, **kw):
        kw.setdefault("mesh", _mesh1())
        kw.setdefault("batch_limit", 8)
        return ParallelInference(model or _FakeModel(), **kw)

    def test_shutdown_drains_queued_callers(self):
        pi = self._pi(_FakeModel(delay=0.1), batch_limit=1, wait_ms=0.0)
        results = []

        def call():
            try:
                pi.output(np.zeros((1, 2), np.float32))
                results.append("ok")
            except ServingError as e:
                results.append(type(e).__name__)

        threads = [threading.Thread(target=call, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.03)
        pi.shutdown()
        for t in threads:
            t.join(5.0)
        assert not any(t.is_alive() for t in threads)  # nobody parked
        assert len(results) == 4
        assert set(results) <= {"ok", "ShutdownError"}
        assert "ShutdownError" in results
        with pytest.raises(ShutdownError):
            pi.output(np.zeros((1, 2), np.float32))

    def test_oversize_request_not_silently_merged(self):
        seen = []
        pi = self._pi(_FakeModel(fn=lambda x: seen.append(x.shape[0])
                                 or x * 2.0),
                      batch_limit=4)
        try:
            x = np.arange(36, dtype=np.float32).reshape(12, 3)
            np.testing.assert_array_equal(pi.output(x), x * 2.0)
            assert 12 in seen  # dispatched alone, past-limit but whole
        finally:
            pi.shutdown()

    def test_coalescing_never_overshoots_limit(self):
        seen = []
        pi = self._pi(_FakeModel(fn=lambda x: seen.append(x.shape[0])
                                 or (time.sleep(0.01), x * 2.0)[1]),
                      batch_limit=4, wait_ms=20.0)
        try:
            import concurrent.futures as cf

            xs = [np.full((3, 2), i, np.float32) for i in range(6)]
            with cf.ThreadPoolExecutor(6) as ex:
                outs = list(ex.map(pi.output, xs))
            for o, x in zip(outs, xs):
                np.testing.assert_array_equal(o, x * 2.0)
            # 3-row requests against limit 4: one per batch — never the
            # old behavior of 3+3=6 rows silently overshooting
            assert max(seen) <= 4
        finally:
            pi.shutdown()

    def test_mismatched_shape_fails_alone(self):
        def picky(x):
            if x.shape[1] != 4:
                raise ValueError("bad trailing shape")
            return x * 2.0

        pi = self._pi(_FakeModel(fn=picky), wait_ms=10.0)
        try:
            import concurrent.futures as cf

            good = np.zeros((2, 4), np.float32)
            bad = np.zeros((2, 5), np.float32)
            with cf.ThreadPoolExecutor(3) as ex:
                f1 = ex.submit(pi.output, good)
                f2 = ex.submit(pi.output, bad)
                f3 = ex.submit(pi.output, good)
                np.testing.assert_array_equal(f1.result(10), good * 2.0)
                np.testing.assert_array_equal(f3.result(10), good * 2.0)
                with pytest.raises(ValueError):
                    f2.result(10)
        finally:
            pi.shutdown()

    def test_dead_dispatcher_surfaces_not_queues_forever(self):
        pi = self._pi(_FakeModel())

        def bomb(batch):
            raise SystemExit("dispatcher bug")

        pi._run_batch = bomb
        with pytest.raises(DispatcherCrashedError):
            pi.output(np.zeros((1, 2), np.float32))
        with pytest.raises(DispatcherCrashedError):
            pi.output(np.zeros((1, 2), np.float32))
        pi.shutdown()

    def test_output_deadline_bounds_the_wait(self):
        pi = self._pi(_FakeModel(delay=0.3), batch_limit=1, wait_ms=0.0)
        try:
            t0 = time.perf_counter()
            with pytest.raises(DeadlineExceededError):
                pi.output(np.zeros((1, 2), np.float32), deadline_s=0.05)
            assert time.perf_counter() - t0 < 0.25
        finally:
            pi.shutdown()

    def test_legacy_request_trace_correlates_across_dispatch(self):
        """The legacy dispatcher speaks the same correlation protocol as
        the serving runtime: one trace per output() call, resolve span
        on the caller, dispatch span on the (named) dispatcher lane,
        flow arrow bound by trace id."""
        trace_mod.configure(enabled=True)
        pi = self._pi()
        try:
            pi.output(np.ones((2, 2), np.float32))
        finally:
            pi.shutdown()
        evs = trace_mod.tracer().to_chrome_trace()["traceEvents"]
        resolves = [e for e in evs if e["name"] == "inference.resolve"]
        assert resolves and resolves[-1]["args"]["outcome"] == "ok"
        tid = resolves[-1]["args"]["trace_id"]
        mine = [e for e in evs
                if (e.get("args") or {}).get("trace_id") == tid]
        disp = [e for e in mine if e["name"] == "inference.dispatch"]
        assert disp and disp[0]["args"]["rows"] == 2
        assert disp[0]["tid"] != resolves[-1]["tid"]  # thread handoff
        flows = [e for e in mine if e["name"] == "inference.batch"]
        assert {e["ph"] for e in flows} == {"s", "f"}
        assert all(e["id"] == tid for e in flows)
        lanes = [e["args"]["name"] for e in evs
                 if e.get("ph") == "M" and e.get("name") == "thread_name"]
        assert "ParallelInference-dispatch" in lanes


# ===========================================================================
# gates
# ===========================================================================


class TestServingGate:
    def test_gate_off_allocates_no_serving_state(self, monkeypatch):
        monkeypatch.delenv("DL4J_TPU_SERVING", raising=False)
        serving_metrics_before = {
            k: v for k, v in metrics_mod.registry().snapshot().items()
            if k.startswith("dl4j_tpu_serving_")}
        pi = ParallelInference(_FakeModel(), mesh=_mesh1())
        try:
            assert pi._serving is None  # legacy dispatcher, nothing more
            out = pi.output(np.ones((2, 3), np.float32))
            np.testing.assert_array_equal(out, np.full((2, 3), 2.0))
            # one legacy dispatcher thread, no serving runtime thread,
            # and not a single serving metric child touched
            assert pi._thread.is_alive()
            assert _serving_threads() == []
            serving_metrics_after = {
                k: v for k, v in metrics_mod.registry().snapshot().items()
                if k.startswith("dl4j_tpu_serving_")}
            assert serving_metrics_after == serving_metrics_before
            assert healthz_section() is None
        finally:
            pi.shutdown()
        assert not pi._thread.is_alive()

    def test_gate_on_routes_through_serving_runtime(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_SERVING", "1")
        pi = ParallelInference(_FakeModel(), mesh=_mesh1(),
                               batch_limit=8)
        try:
            assert isinstance(pi._serving, InferenceServer)
            out = pi.output(np.ones((2, 3), np.float32), deadline_s=5.0)
            np.testing.assert_array_equal(out, np.full((2, 3), 2.0))
            assert _counter("dl4j_tpu_serving_requests_total")[
                "outcome=ok"] == 1.0
        finally:
            pi.shutdown()
        assert pi._serving.stopped


class TestDispatchedBatches:
    def test_snapshot_counts_the_calls_of_the_dispatch_callable(self):
        """ISSUE 24: `snapshot()["dispatched_batches"]` is a count (where
        `dispatched_rows` is a set of shapes): the calls a wrapped
        dispatch callable saw, a failing one included."""
        calls = []

        def dispatch(x):
            calls.append(x.shape[0])
            if len(calls) == 2:
                raise RuntimeError("boom")
            return x * 2.0

        s = _server(dispatch=dispatch)
        try:
            assert s.snapshot()["dispatched_batches"] == 0
            for i in range(4):   # one at a time: one batch each
                req = s.submit(np.ones((1, 3), np.float32))
                if i == 1:
                    with pytest.raises(ServingError):
                        s.result(req)
                else:
                    np.testing.assert_allclose(s.result(req), 2.0)
            assert len(calls) == 4
            assert s.snapshot()["dispatched_batches"] == len(calls)
        finally:
            s.shutdown()
        coalesce = trace_mod.tracer().account.snapshot()["serving.coalesce"]
        assert coalesce["calls"] >= 4
