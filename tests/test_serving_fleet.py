"""Multi-model serving fleet (ISSUE 11 acceptance): ModelRegistry
hosting named, versioned models each behind its own InferenceServer;
Router's deterministic traffic split + SLO-gated canary rollout — a
chaos-broken canary must roll back within one evaluation tick, never
reach 100%, and leave exactly ONE canary_rollback flight bundle with
the offending trace ids, while a fault-free canary promotes; persisted
warm starts — a restarted replica's warmup performs ZERO cold compiles
(compile-watcher-asserted against the persistent compilation cache);
flight-bundle rotation (DL4J_TPU_FLIGHT_KEEP); the blessed client
retry loop (submit_with_retry honoring retry_after_s); and the
`serve rollout` / `postmortem --reason` CLI surfaces."""
import json
import os
import time
import urllib.request
import weakref

import numpy as np
import pytest

from deeplearning4j_tpu.resilience import chaos
from deeplearning4j_tpu.serving import CircuitBreaker
from deeplearning4j_tpu.serving.buckets import BucketSpec
from deeplearning4j_tpu.serving.client import submit_with_retry
from deeplearning4j_tpu.serving.errors import (
    CircuitOpenError,
    DispatchFailedError,
    ShedError,
)
from deeplearning4j_tpu.serving.registry import (
    ModelRegistry,
    resolve_model,
)
from deeplearning4j_tpu.serving.router import Rollout, Router
from deeplearning4j_tpu.serving import warmstart
from deeplearning4j_tpu.telemetry import flight as flight_mod
from deeplearning4j_tpu.telemetry import metrics as metrics_mod
from deeplearning4j_tpu.telemetry import slo as slo_mod
from deeplearning4j_tpu.telemetry import trace as trace_mod


@pytest.fixture(autouse=True)
def _clean(monkeypatch, tmp_path):
    monkeypatch.setenv("DL4J_TPU_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.delenv("DL4J_TPU_CHAOS", raising=False)
    monkeypatch.delenv("DL4J_TPU_WARM_CACHE", raising=False)
    monkeypatch.delenv("DL4J_TPU_FLIGHT_KEEP", raising=False)
    trace_mod.configure(enabled=None)
    metrics_mod.registry().reset()
    slo_mod.reset_for_tests()
    chaos.reset_fault_points()
    yield
    trace_mod.configure(enabled=None)
    # drop this test's spans from the process-global ring: later test
    # files (test_slo.py) join offending traces from it and must not
    # see our DispatchFailedError resolves
    trace_mod.tracer()._buf.clear()
    metrics_mod.registry().reset()
    slo_mod.reset_for_tests()
    chaos.reset_fault_points()


def _echo(mult=1.0):
    return lambda xp: np.asarray(xp, dtype=np.float32) * mult


def _register(reg, name="m", version="v1", mult=1.0, **kw):
    kw.setdefault("breaker", CircuitBreaker(failure_threshold=1000))
    kw.setdefault("batch_limit", 8)
    kw.setdefault("buckets", BucketSpec(8, sizes=(1, 8)))
    return reg.register(name, dispatch=_echo(mult), version=version, **kw)


def _family_total(name):
    fam = metrics_mod.registry().get(name)
    if fam is None:
        return 0.0
    return sum(child.value for _, child in fam.child_items())


def _bundles(tmp_path, reason):
    d = tmp_path / "flight"
    if not d.is_dir():
        return []
    return sorted(str(d / p) for p in os.listdir(d) if reason in p)


# ===========================================================================
# registry
# ===========================================================================


class TestModelRegistry:
    def test_versions_stable_and_snapshot(self):
        reg = ModelRegistry()
        try:
            _register(reg, "m", "v1")
            _register(reg, "m", "v2", stable=False)
            _register(reg, "other", "v1")
            assert reg.models() == ["m", "other"]
            # first version registered is stable; v2 rode in beside it
            assert reg.get("m").version == "v1"
            assert reg.get("m", "v2").key == "m:v2"
            reg.set_stable("m", "v2")
            assert reg.get("m").version == "v2"
            snap = reg.snapshot()
            assert snap["models"]["m"]["stable"] == "v2"
            assert [v["version"] for v in
                    snap["models"]["m"]["versions"]] == ["v1", "v2"]
            with pytest.raises(ValueError):
                _register(reg, "m", "v2")  # duplicate
            with pytest.raises(KeyError):
                reg.get("nope")
        finally:
            reg.shutdown()

    def test_isolation_one_model_serves_while_another_fails(self):
        """Per-model servers: one model's dispatch failures never touch
        a neighbor's traffic (the fleet's whole point)."""
        reg = ModelRegistry()
        try:
            def boom(xp):
                raise RuntimeError("broken model")
            reg.register("bad", dispatch=boom,
                         breaker=CircuitBreaker(failure_threshold=1000),
                         buckets=BucketSpec(8, sizes=(1, 8)))
            _register(reg, "good")
            with pytest.raises(DispatchFailedError):
                reg.get("bad").server.output(np.ones((1, 2), np.float32))
            out = reg.get("good").server.output(
                np.ones((1, 2), np.float32))
            assert out.shape == (1, 2)
        finally:
            reg.shutdown()

    def test_unregister_drains_and_repoints_stable(self):
        reg = ModelRegistry()
        try:
            _register(reg, "m", "v1")
            _register(reg, "m", "v2", stable=False)
            reg.unregister("m", "v1")
            # the surviving version inherits stable
            assert reg.get("m").version == "v2"
            reg.unregister("m")
            assert reg.models() == []
        finally:
            reg.shutdown()

    def test_resolve_model_sources(self):
        # a non-string source passes through untouched
        sentinel = object()
        assert resolve_model(sentinel) is sentinel
        with pytest.raises(ValueError):
            resolve_model("zoo:NoSuchModel")
        with pytest.raises(ValueError):
            resolve_model("not-a-source")

    def test_canary_chaos_points_armed_only_while_canary(self, monkeypatch):
        """DL4J_TPU_CHAOS=canary_dispatch@1 must break the FIRST canary
        batch, not the stable traffic or warmups that ran before it."""
        monkeypatch.setenv("DL4J_TPU_CHAOS", "canary_dispatch@1")
        chaos.reset_fault_points()
        reg = ModelRegistry()
        try:
            mv = _register(reg, "m", "v1")
            x = np.ones((1, 2), np.float32)
            reg.warm("m", example=x)  # consumes nothing
            mv.server.output(x)       # stable traffic: schedule untouched
            mv.canary = True
            with pytest.raises(DispatchFailedError):
                mv.server.output(x)   # the 1st CANARY batch fires
            mv.canary = False
            assert mv.server.output(x).shape == (1, 2)
        finally:
            reg.shutdown()


# ===========================================================================
# router traffic split
# ===========================================================================


class TestRouterSplit:
    def test_counter_split_is_exact(self):
        """fraction f is realized exactly: 40 requests at f=0.25 put
        precisely 10 on the canary, at deterministic positions."""
        reg = ModelRegistry()
        try:
            _register(reg, "m", "v1", mult=1.0)
            _register(reg, "m", "v2", mult=2.0, stable=False)
            router = Router(reg)
            ro = router.start_rollout("m", "v2", stages=(0.25,),
                                      min_requests=10 ** 6)
            x = np.ones((1, 2), np.float32)
            hits = [float(router.output("m", x)[0, 0]) for _ in range(40)]
            assert hits.count(2.0) == 10
            # request n routes canary iff floor(n/4) advanced: 4, 8, ...
            assert [i + 1 for i, h in enumerate(hits)
                    if h == 2.0] == [4, 8, 12, 16, 20, 24, 28, 32, 36, 40]
            assert ro.canary_requests_in_stage == 10
        finally:
            reg.shutdown()

    def test_no_rollout_all_stable(self):
        reg = ModelRegistry()
        try:
            _register(reg, "m", "v1", mult=1.0)
            _register(reg, "m", "v2", mult=2.0, stable=False)
            router = Router(reg)
            x = np.ones((1, 2), np.float32)
            assert all(float(router.output("m", x)[0, 0]) == 1.0
                       for _ in range(10))
        finally:
            reg.shutdown()

    def test_start_rollout_validation(self):
        reg = ModelRegistry()
        try:
            _register(reg, "m", "v1")
            _register(reg, "m", "v2", stable=False)
            router = Router(reg)
            with pytest.raises(KeyError):
                router.start_rollout("m", "v9")
            with pytest.raises(ValueError):
                router.start_rollout("m", "v1")  # canary == stable
            with pytest.raises(ValueError):
                Rollout("m", "v1", "v2", stages=(0.0,), min_requests=1)
            router.start_rollout("m", "v2", stages=(0.5, 1.0),
                                 min_requests=1)
            with pytest.raises(ValueError):
                router.start_rollout("m", "v2")  # already running
        finally:
            reg.shutdown()


# ===========================================================================
# canary rollout: the acceptance arcs
# ===========================================================================


def _fleet_with_rollout(stages, min_requests, rule_kwargs=None):
    reg = ModelRegistry()
    _register(reg, "m", "v1", mult=1.0)
    _register(reg, "m", "v2", mult=2.0, stable=False)
    router = Router(reg)
    ro = router.start_rollout("m", "v2", stages=stages,
                              min_requests=min_requests,
                              **(rule_kwargs or {}))
    return reg, router, ro


class TestCanaryRollout:
    def test_broken_canary_rolls_back_within_one_tick(self, monkeypatch,
                                                      tmp_path):
        """The headline chaos arc: every canary batch raises; one SLO
        tick after the burn the rollout is rolled back — the ramp
        freezes, traffic snaps to stable, and exactly ONE
        canary_rollback bundle carries the offending trace ids."""
        trace_mod.configure(enabled=True)
        monkeypatch.setenv(
            "DL4J_TPU_CHAOS",
            "canary_dispatch@" + ":".join(str(i) for i in range(1, 50)))
        chaos.reset_fault_points()
        reg, router, ro = _fleet_with_rollout((0.5, 1.0), 50)
        try:
            router.evaluate(now=1000.0)  # baseline sample (burn = delta)
            x = np.ones((1, 2), np.float32)
            ok = err = 0
            for _ in range(20):
                try:
                    router.output("m", x)
                    ok += 1
                except DispatchFailedError:
                    err += 1
            assert (ok, err) == (10, 10)  # f=0.5, split exact
            router.evaluate(now=1061.0)  # ONE tick past the fast window
            assert ro.state == Rollout.ROLLED_BACK
            assert ro.history[-1] == "rollback"
            assert "100" not in ro.history  # never reached full ramp
            assert ro.fraction == 0.0
            assert any(name.startswith("serving_availability:m:v2")
                       for name in ro.rollback_rules)
            # exactly one canary_rollback bundle, offending traces inside
            bundles = _bundles(tmp_path, "canary_rollback")
            assert len(bundles) == 1
            with open(bundles[0]) as f:
                doc = json.load(f)
            assert doc["canary"]["model"] == "m"
            assert doc["canary"]["canary"] == "v2"
            assert doc["canary"]["rules"]
            assert len(doc["canary"]["offending_traces"]) > 0
            # the ramp is frozen: more traffic + ticks change nothing,
            # and 100% of it lands on stable (remaining chaos hits are
            # never consumed — the canary flag was disarmed)
            for _ in range(10):
                assert float(router.output("m", x)[0, 0]) == 1.0
            router.evaluate(now=1122.0)
            assert ro.state == Rollout.ROLLED_BACK
            assert len(_bundles(tmp_path, "canary_rollback")) == 1
        finally:
            reg.shutdown()

    def test_nan_canary_rolls_back(self, monkeypatch, tmp_path):
        """canary_nan (silent): outputs go non-finite, the runtime's
        NaN discipline turns them into bad outcomes, the per-version
        availability SLO burns, rollback."""
        trace_mod.configure(enabled=True)
        monkeypatch.setenv(
            "DL4J_TPU_CHAOS",
            "canary_nan@" + ":".join(str(i) for i in range(1, 50)))
        chaos.reset_fault_points()
        reg, router, ro = _fleet_with_rollout((0.5, 1.0), 50)
        try:
            router.evaluate(now=1000.0)
            x = np.ones((1, 2), np.float32)
            failures = 0
            for _ in range(20):
                try:
                    router.output("m", x)
                except Exception:
                    failures += 1
            assert failures == 10
            router.evaluate(now=1061.0)
            assert ro.state == Rollout.ROLLED_BACK
            assert len(_bundles(tmp_path, "canary_rollback")) == 1
        finally:
            reg.shutdown()

    def test_healthy_canary_promotes_to_stable(self, tmp_path):
        """The fault-free arc: the canary soaks every stage and is
        promoted — it becomes the registry's stable version; no
        rollback bundle exists."""
        trace_mod.configure(enabled=True)
        reg, router, ro = _fleet_with_rollout((0.5, 1.0), 5)
        try:
            x = np.ones((1, 2), np.float32)
            router.evaluate(now=1000.0)
            now = 1000.0
            for _ in range(6):  # bounded control loop, promotes inside
                if ro.state != Rollout.RUNNING:
                    break
                for _ in range(20):
                    router.output("m", x)
                now += 61.0
                router.evaluate(now=now)
            assert ro.state == Rollout.PROMOTED
            assert ro.history[-1] == "promote"
            assert reg.get("m").version == "v2"  # canary IS stable now
            assert not _bundles(tmp_path, "canary_rollback")
            # transitions counter saw every ramp stage + the promote
            fam = metrics_mod.registry().get(
                "dl4j_tpu_canary_transitions_total")
            stages_seen = {labels["stage"]
                           for labels, _ in fam.child_items()}
            assert {"50", "100", "promote"} <= stages_seen
        finally:
            reg.shutdown()

    def test_ramp_holds_until_min_requests(self):
        """A stage without enough canary soak never advances, firing or
        not — promotion requires evidence, not elapsed time."""
        trace_mod.configure(enabled=True)
        reg, router, ro = _fleet_with_rollout((0.5, 1.0), 50)
        try:
            x = np.ones((1, 2), np.float32)
            router.evaluate(now=1000.0)
            for _ in range(20):  # only 10 canary requests of 50 needed
                router.output("m", x)
            router.evaluate(now=1061.0)
            assert ro.state == Rollout.RUNNING
            assert ro.stage == 0
        finally:
            reg.shutdown()


# ===========================================================================
# persisted warm starts: the zero-cold-start acceptance arc
# ===========================================================================


class TestWarmStart:
    def test_restarted_replica_warms_with_zero_cold_compiles(self, tmp_path):
        """Boot a registry against a warm-cache dir, warm (cold
        compiles happen, manifest recorded), tear down. Boot a FRESH
        jit wrapper against the same dir — the process-restart
        simulation — and warm purely from the manifest: the compile
        watcher must count zero cold compiles (every backend-compile
        event is matched by a persistent-cache retrieval), the retrace
        detector stays silent, and the first request lands inside the
        latency SLO."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.telemetry import introspect

        # the compile watcher's jax.monitoring listener is telemetry-
        # gated; the zero-cold-start assertion needs it counting
        trace_mod.configure(enabled=True)
        watcher = introspect.watcher()  # installs the monitoring listener
        cache = str(tmp_path / "warmcache")

        def make_dispatch():
            # a FRESH jax.jit wrapper per boot: new trace, same lowered
            # HLO fingerprint — exactly what a restarted process does
            fwd = jax.jit(lambda v: jnp.tanh(v * 3.0) + 1.5)
            return lambda xp: np.asarray(fwd(jnp.asarray(xp)))

        def boot():
            reg = ModelRegistry(warm_cache_dir=cache)
            reg.register("m", dispatch=make_dispatch(),
                         buckets=BucketSpec(8, sizes=(1, 4)),
                         breaker=CircuitBreaker(failure_threshold=1000))
            return reg

        try:
            # ---- boot 1: cold, records the manifest ----
            reg1 = boot()
            reg1.warm("m", example=np.ones((1, 3), np.float32))
            assert warmstart.load_manifest(cache, "m", "v1") is not None
            reg1.shutdown()

            # ---- boot 2: manifest-driven warmup, zero cold compiles ----
            cold_before = watcher.cold_compile_count()
            backend_before = watcher.compile_count()
            retrace_before = _family_total(
                "dl4j_tpu_retrace_warnings_total")
            reg2 = boot()
            reg2.warm("m")  # no example: synthesized from the manifest
            assert watcher.compile_count() > backend_before, \
                "warmup must have traced (the restart was real)"
            assert watcher.cold_compile_count() == cold_before, \
                "a restarted replica's warmup must be a disk read"
            assert _family_total(
                "dl4j_tpu_retrace_warnings_total") == retrace_before
            # first request is served warm, inside the latency SLO
            t0 = time.perf_counter()
            out = reg2.get("m").server.output(np.ones((1, 3), np.float32))
            assert time.perf_counter() - t0 < 0.25
            assert out.shape == (1, 3)
            reg2.shutdown()
        finally:
            # persist-every-compile is process-global config: restore
            # the default floor so later tests don't spool every tiny
            # executable to disk
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 1.0)

    def test_warm_without_cache_or_manifest_raises(self, tmp_path):
        reg = ModelRegistry()  # no cache dir
        try:
            _register(reg, "m")
            with pytest.raises(ValueError):
                reg.warm("m")
        finally:
            reg.shutdown()
        import jax

        reg2 = ModelRegistry(warm_cache_dir=str(tmp_path / "wc"))
        try:
            _register(reg2, "m")
            with pytest.raises(FileNotFoundError):
                reg2.warm("m")  # cache dir exists, no manifest yet
        finally:
            reg2.shutdown()
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 1.0)

    def test_manifest_roundtrip_and_slug(self, tmp_path):
        d = str(tmp_path / "wc")
        os.makedirs(d)
        x = np.zeros((4, 7), np.float32)
        warmstart.record_warm(d, "model/with:odd chars", "v1.2", x, (1, 8))
        m = warmstart.load_manifest(d, "model/with:odd chars", "v1.2")
        assert m["row_shape"] == [7]
        assert m["buckets"] == [1, 8]
        ex = warmstart.warmup_example(m)
        assert ex.shape == (1, 7) and ex.dtype == np.float32
        assert len(warmstart.list_manifests(d)) == 1
        # the slug keeps the filename filesystem-safe
        assert "/" not in os.path.basename(
            warmstart.manifest_path(d, "model/with:odd chars", "v1.2"))


# ===========================================================================
# flight-bundle rotation
# ===========================================================================


class TestFlightRotation:
    def test_keep_prunes_oldest(self, monkeypatch, tmp_path):
        trace_mod.configure(enabled=True)
        monkeypatch.setenv("DL4J_TPU_FLIGHT_KEEP", "3")
        paths = [flight_mod.dump("rot_test", note=str(i))
                 for i in range(6)]
        assert all(paths)
        left = flight_mod.list_bundles(str(tmp_path / "flight"))
        assert len(left) == 3
        # the newest three survive (filenames sort chronologically)
        assert [os.path.basename(p) for p in left] == \
            [os.path.basename(p) for p in paths[-3:]]

    def test_keep_zero_disables_rotation(self, monkeypatch, tmp_path):
        trace_mod.configure(enabled=True)
        monkeypatch.setenv("DL4J_TPU_FLIGHT_KEEP", "0")
        for i in range(25):
            flight_mod.dump("rot_test", note=str(i))
        assert len(flight_mod.list_bundles(str(tmp_path / "flight"))) == 25

    def test_default_keep_is_twenty(self, tmp_path):
        trace_mod.configure(enabled=True)
        for i in range(23):
            flight_mod.dump("rot_test", note=str(i))
        assert len(flight_mod.list_bundles(str(tmp_path / "flight"))) == 20


# ===========================================================================
# blessed client retry loop
# ===========================================================================


class _FlakyServer:
    """Sheds `fail_n` times (with a retry_after_s hint), then answers."""

    def __init__(self, fail_n, exc=ShedError, hint=None):
        self.fail_n = fail_n
        self.exc = exc
        self.hint = hint
        self.calls = 0

    def output(self, x, deadline_s=None):
        self.calls += 1
        if self.calls <= self.fail_n:
            if self.hint is not None:
                raise self.exc("refused", retry_after_s=self.hint)
            raise self.exc("refused")
        return np.asarray(x) * 10.0


class TestSubmitWithRetry:
    def test_rides_out_transient_sheds(self):
        srv = _FlakyServer(2)
        sleeps = []
        out = submit_with_retry(srv, np.ones(2), sleep=sleeps.append,
                                rng=__import__("random").Random(7))
        assert float(out[0]) == 10.0
        assert srv.calls == 3 and len(sleeps) == 2
        assert all(s > 0 for s in sleeps)

    def test_honors_retry_after_hint(self):
        # the runtime says capacity returns in 1.7s: every sleep is at
        # least that, however small the jittered backoff draw came out
        srv = _FlakyServer(2, exc=CircuitOpenError, hint=1.7)
        sleeps = []
        submit_with_retry(srv, np.ones(2), sleep=sleeps.append,
                          rng=__import__("random").Random(7))
        assert all(s >= 1.7 for s in sleeps)

    def test_non_transient_raises_immediately(self):
        srv = _FlakyServer(5, exc=DispatchFailedError)
        sleeps = []
        with pytest.raises(DispatchFailedError):
            submit_with_retry(srv, np.ones(2), sleep=sleeps.append)
        assert srv.calls == 1 and not sleeps

    def test_attempts_exhausted_reraises_last(self):
        srv = _FlakyServer(99)
        with pytest.raises(ShedError):
            submit_with_retry(srv, np.ones(2), attempts=3,
                              sleep=lambda s: None)
        assert srv.calls == 3

    def test_deadline_bounds_the_whole_operation(self):
        srv = _FlakyServer(99, hint=50.0)
        sleeps = []
        with pytest.raises(ShedError):
            submit_with_retry(srv, np.ones(2), attempts=50,
                              deadline_s=0.0, sleep=sleeps.append)
        # expired deadline: no sleeping toward a refusal we can't outwait
        assert srv.calls <= 2

    def test_routes_through_router_with_model(self):
        reg = ModelRegistry()
        try:
            _register(reg, "m")
            router = Router(reg)
            out = submit_with_retry(router, np.ones((1, 2), np.float32),
                                    model="m")
            assert out.shape == (1, 2)
        finally:
            reg.shutdown()


# ===========================================================================
# /models + CLI surfaces
# ===========================================================================


class TestEndpointsAndCli:
    def test_models_section_none_without_fleet(self, monkeypatch):
        from deeplearning4j_tpu.serving import registry as registry_mod
        from deeplearning4j_tpu.serving import router as router_mod

        monkeypatch.setattr(router_mod, "_ROUTERS", weakref.WeakSet())
        monkeypatch.setattr(registry_mod, "_REGISTRIES", weakref.WeakSet())
        assert router_mod.models_section() is None

    def test_models_endpoint_and_healthz_merge(self):
        import gc

        from deeplearning4j_tpu.ui.server import UIServer

        gc.collect()  # drop earlier tests' routers from the WeakSet
        reg = ModelRegistry()
        srv = None
        try:
            _register(reg, "m", "v1")
            _register(reg, "m", "v2", stable=False)
            router = Router(reg)
            router.start_rollout("m", "v2", stages=(0.5, 1.0),
                                 min_requests=1)
            srv = UIServer(port=0)
            doc = json.loads(urllib.request.urlopen(
                srv.url() + "/models").read())
            assert doc["models"]["m"]["stable"] == "v1"
            assert doc["rollouts"][0]["state"] == "running"
            health = json.loads(urllib.request.urlopen(
                srv.url() + "/healthz").read())
            assert health["models"]["rollouts"][0]["canary"] == "v2"
        finally:
            if srv is not None:
                srv.stop()
            reg.shutdown()

    def test_serve_rollout_cli_exit_codes(self, capsys):
        import gc

        from deeplearning4j_tpu import cli
        from deeplearning4j_tpu.ui.server import UIServer

        gc.collect()  # drop earlier tests' routers from the WeakSet
        reg = ModelRegistry()
        srv = None
        try:
            _register(reg, "m", "v1")
            _register(reg, "m", "v2", stable=False)
            router = Router(reg)
            ro = router.start_rollout("m", "v2", stages=(0.5, 1.0),
                                      min_requests=1)
            srv = UIServer(port=0)
            assert cli.main(["serve", "rollout", "--url", srv.url()]) == 0
            assert "running" in capsys.readouterr().out
            ro.state = Rollout.ROLLED_BACK  # the pager-visible state
            assert cli.main(["serve", "rollout", "--url", srv.url()]) == 2
        finally:
            if srv is not None:
                srv.stop()
            reg.shutdown()
        assert cli.main(["serve", "rollout",
                         "--url", "http://127.0.0.1:1"]) == 1

    def test_postmortem_reason_filter(self, tmp_path, capsys):
        from deeplearning4j_tpu import cli

        trace_mod.configure(enabled=True)
        flight_mod.dump("canary_rollback", note="m:v2")
        flight_mod.dump("slo_burn", note="other")
        d = str(tmp_path / "flight")
        assert cli.main(["postmortem", "--dir", d,
                         "--reason", "canary_rollback", "--json"]) == 0
        rows = json.loads(capsys.readouterr().out)
        assert len(rows) == 1
        assert rows[0]["reason"] == "canary_rollback"
        assert cli.main(["postmortem", "--dir", d,
                         "--reason", "nonexistent"]) == 1
