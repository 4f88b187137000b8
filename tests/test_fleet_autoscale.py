"""Elastic fleet autoscaling + multi-tenant fairness (ISSUE 17
acceptance): an Autoscaler pool behind the Router that scales out on a
2x offered-load step with ZERO cold compiles and no availability-SLO
burn episode; per-tenant token-bucket quotas + deficit-round-robin
fair queueing so a `tenant_burst` chaos storm sheds ONLY the noisy
tenant (typed TenantQuotaError) while the quiet tenant's p99 and shed
rate stay flat; a replica crash mid-dispatch evicts the replica and
every in-flight request resolves typed; spawn failures (chaos
`replica_spawn`) retry with decorrelated backoff writing ONE flight
bundle per failure episode; the scale-storm dwell guard; the
breaker-cooldown floor under ShedError.retry_after_s; and the
`serve fleet` CLI / `/fleet` endpoint / `/healthz` fleet-section
surfaces."""
import json
import os
import threading
import time
import urllib.error
import urllib.request
import warnings

import numpy as np
import pytest

from deeplearning4j_tpu.distributed.membership import (
    MembershipRegistry,
    WorkerState,
)
from deeplearning4j_tpu.resilience import chaos
from deeplearning4j_tpu.serving import CircuitBreaker
from deeplearning4j_tpu.serving.autoscaler import (
    Autoscaler,
    fleet_section,
)
from deeplearning4j_tpu.serving.buckets import BucketSpec
from deeplearning4j_tpu.serving.client import submit_with_retry
from deeplearning4j_tpu.serving.errors import (
    DispatcherCrashedError,
    ServingError,
    ShedError,
    TenantQuotaError,
)
from deeplearning4j_tpu.serving.registry import ModelRegistry
from deeplearning4j_tpu.serving.router import Router
from deeplearning4j_tpu.serving.runtime import InferenceServer
from deeplearning4j_tpu.serving.tenancy import (
    BURST_FACTOR,
    DEFAULT_TENANT,
    TenancyController,
    TokenBucket,
)
from deeplearning4j_tpu.telemetry import metrics as metrics_mod
from deeplearning4j_tpu.telemetry import slo as slo_mod
from deeplearning4j_tpu.telemetry import trace as trace_mod


@pytest.fixture(autouse=True)
def _clean(monkeypatch, tmp_path):
    monkeypatch.setenv("DL4J_TPU_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.delenv("DL4J_TPU_CHAOS", raising=False)
    monkeypatch.delenv("DL4J_TPU_WARM_CACHE", raising=False)
    trace_mod.configure(enabled=None)
    metrics_mod.registry().reset()
    slo_mod.reset_for_tests()
    chaos.reset_fault_points()
    yield
    trace_mod.configure(enabled=None)
    trace_mod.tracer()._buf.clear()
    metrics_mod.registry().reset()
    slo_mod.reset_for_tests()
    chaos.reset_fault_points()


def _echo(xp):
    return np.asarray(xp, dtype=np.float32)


def _server(**kw):
    kw.setdefault("dispatch", _echo)
    kw.setdefault("batch_limit", 8)
    kw.setdefault("buckets", BucketSpec(8, sizes=(1, 8)))
    kw.setdefault("breaker", CircuitBreaker(failure_threshold=1000))
    return InferenceServer(**kw)


def _factory(**server_kw):
    def make(name, tenancy):
        return _server(name=name, tenancy=tenancy, **server_kw)
    return make


def _pool(**kw):
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 3)
    kw.setdefault("min_dwell_s", 0.0)
    factory = kw.pop("factory", None) or _factory()
    return Autoscaler(factory, **kw)


def _counter(name):
    fam = metrics_mod.registry().get(name)
    if fam is None:
        return {}
    return {",".join(f"{k}={v}" for k, v in sorted(labels.items())):
            child.value for labels, child in fam.child_items()}


def _bundles(tmp_path, reason):
    d = tmp_path / "flight"
    if not d.is_dir():
        return []
    return sorted(str(d / p) for p in os.listdir(d) if reason in p)


class _Req:
    """Minimal request stand-in for direct TenantQueue tests."""

    def __init__(self, tenant, n=1, tag=""):
        self.tenant = tenant
        self.n = n
        self.tag = tag

    def __repr__(self):
        return f"req({self.tenant}:{self.tag})"


# ===========================================================================
# token bucket + DRR queue units
# ===========================================================================


class TestTokenBucket:
    def test_spend_refill_and_wait_hint(self):
        b = TokenBucket(rate=10.0, burst=5.0, now=0.0)
        assert b.take(5, now=0.0) == 0.0  # full burst spends
        wait = b.take(1, now=0.0)
        assert wait == pytest.approx(0.1)  # 1 token at 10/s
        assert b.take(1, now=0.2) == 0.0  # refilled past the cost
        # cost larger than burst: hint is the time to a FULL bucket,
        # never infinity
        b2 = TokenBucket(rate=1.0, burst=2.0, now=0.0)
        assert b2.take(2, now=0.0) == 0.0
        hint = b2.take(100, now=0.0)
        assert 0.0 < hint <= 2.0

    def test_never_exceeds_burst(self):
        b = TokenBucket(rate=1000.0, burst=2.0, now=0.0)
        assert b.take(2, now=100.0) == 0.0  # long idle caps at burst
        assert b.take(1, now=100.0) > 0.0


class TestTenantQueueDRR:
    def _queue(self, weights, quantum=1):
        ctrl = TenancyController(default_rate=1e9, quantum=quantum)
        for name, w in weights.items():
            ctrl.add_tenant(name, rate=1e9, weight=w)
        return ctrl.make_queue(queue_limit=64)

    def test_equal_weights_alternate(self):
        q = self._queue({"a": 1.0, "b": 1.0})
        for i in range(3):
            q.append(_Req("a", tag=str(i)))
        for i in range(3):
            q.append(_Req("b", tag=str(i)))
        order = [(q.popleft().tenant) for _ in range(6)]
        assert order == ["a", "b", "a", "b", "a", "b"]

    def test_weighted_share(self):
        q = self._queue({"w2": 2.0, "w1": 1.0})
        for i in range(12):
            q.append(_Req("w2", tag=str(i)))
            q.append(_Req("w1", tag=str(i)))
        first = [q.popleft().tenant for _ in range(12)]
        # a 2:1 weighting serves ~8 of the first 12 from the heavy tenant
        assert first.count("w2") == 8
        assert first.count("w1") == 4

    def test_deficit_accumulates_for_large_head(self):
        # an 8-row head must WAIT until its tenant's deficit covers it;
        # the 1-row tenant keeps being served meanwhile
        q = self._queue({"big": 1.0, "small": 1.0}, quantum=1)
        q.append(_Req("big", n=8))
        q.append(_Req("small", n=1))
        got = [q.popleft() for _ in range(2)]
        assert [g.tenant for g in got] == ["small", "big"]

    def test_peek_equals_pop(self):
        q = self._queue({"a": 1.0, "b": 3.0})
        for i in range(4):
            q.append(_Req("a", tag=f"a{i}"))
            q.append(_Req("b", tag=f"b{i}"))
        while q:
            head = q[0]
            assert q.popleft() is head

    def test_deque_surface(self):
        q = self._queue({"a": 1.0})
        assert not q and len(q) == 0
        with pytest.raises(IndexError):
            q.popleft()
        r1, r2 = _Req("a", tag="1"), _Req("a", tag="2")
        q.append(r1)
        q.append(r2)
        assert q and len(q) == 2
        assert list(q) == [r1, r2]
        q.remove(r1)
        assert len(q) == 1
        with pytest.raises(ValueError):
            q.remove(r1)
        assert q.queued_by_tenant() == {"a": 1}
        q.clear()
        assert len(q) == 0

    def test_idle_tenant_forfeits_deficit(self):
        q = self._queue({"a": 1.0, "b": 1.0})
        q.append(_Req("a"))
        assert q.popleft().tenant == "a"
        # b was never queued; when it shows up later it gets a fresh
        # quantum, not hoarded credit — a stays competitive
        q.append(_Req("b"))
        q.append(_Req("a"))
        assert {q.popleft().tenant, q.popleft().tenant} == {"a", "b"}


# ===========================================================================
# tenant admission (quota) + per-tenant SLO slices
# ===========================================================================


class TestTenantAdmission:
    def test_over_quota_sheds_typed_with_retry_hint(self):
        ctrl = TenancyController(clock=lambda: 0.0)
        ctrl.add_tenant("acme", rate=10.0, burst=2.0)
        assert ctrl.admit("acme") == "acme"
        assert ctrl.admit("acme") == "acme"
        with pytest.raises(TenantQuotaError) as ei:
            ctrl.admit("acme")
        assert ei.value.tenant == "acme"
        assert ei.value.retry_after_s == pytest.approx(0.1)
        assert isinstance(ei.value, ShedError)  # retry loops back off
        sheds = _counter("dl4j_tpu_tenant_shed_total")
        assert sheds.get("reason=quota,tenant=acme") == 1.0

    def test_server_quota_gate_before_queue(self):
        s = _server(tenancy=TenancyController(default_rate=1e9),
                    queue_limit=4)
        try:
            s.tenancy.add_tenant("t", rate=0.001, burst=1.0)
            out = s.output(np.ones((1, 2), np.float32), tenant="t")
            assert out.shape == (1, 2)
            with pytest.raises(TenantQuotaError):
                s.output(np.ones((1, 2), np.float32), tenant="t")
            # the shared queue never saw the refused request
            assert s.snapshot()["queue_depth"] == 0
            reqs = _counter("dl4j_tpu_tenant_requests_total")
            assert reqs.get("outcome=ok,tenant=t") == 1.0
        finally:
            s.shutdown()

    def test_submit_with_retry_rides_out_quota(self):
        s = _server(tenancy=TenancyController(default_rate=50.0,
                                              default_burst=1.0))
        try:
            naps = []

            def nap(seconds):
                naps.append(seconds)
                time.sleep(seconds)

            for _ in range(3):
                out = submit_with_retry(
                    s, np.ones((1, 2), np.float32),
                    base_backoff_s=0.001, sleep=nap)
                assert out.shape == (1, 2)
            # at 50 rows/s with burst 1 the later submits must have
            # waited on the quota hint at least once
            assert naps and all(n > 0 for n in naps)
        finally:
            s.shutdown()

    def test_tenant_rules_slices(self):
        rules = slo_mod.tenant_rules("acme")
        names = [r.name for r in rules]
        assert names == ["tenant_availability:acme",
                         "tenant_latency:acme",
                         "tenant_shed_rate:acme"]
        avail = rules[0]
        assert avail.bad[0].metric == "dl4j_tpu_tenant_requests_total"
        assert avail.bad[0].include == {"tenant": ("acme",)}
        assert avail.bad[0].exclude == {"outcome": ("ok",)}
        lat = rules[1]
        assert lat.histogram == "dl4j_tpu_tenant_latency_seconds"
        assert lat.histogram_include == {"tenant": ("acme",)}


# ===========================================================================
# satellite: breaker cooldown floors the shed retry hint
# ===========================================================================


class TestShedRetryHintBreakerFloor:
    def test_hint_floors_at_breaker_cooldown(self):
        br = CircuitBreaker(failure_threshold=1, cooldown_s=30.0)
        s = _server(breaker=br)
        try:
            br.record_failure("boom")  # opens, 30s cooldown
            with s._cond:
                hint = s._retry_hint_locked(est=0.01)
            # queue-pressure estimate alone says 10ms; the breaker says
            # nothing will be served for ~30s — the hint must not lie
            assert hint >= 29.0
            with s._cond:
                assert s._retry_hint_locked(est=100.0) == 100.0
        finally:
            s.shutdown()

    def test_queue_full_shed_carries_floored_hint(self):
        gate = threading.Event()

        def slow(xp):
            gate.wait(5.0)
            return np.asarray(xp, dtype=np.float32)

        br = CircuitBreaker(failure_threshold=1000, cooldown_s=7.0)
        s = _server(dispatch=slow, queue_limit=1, batch_limit=1,
                    buckets=BucketSpec(1, sizes=(1,)), breaker=br,
                    wait_ms=0.0)
        try:
            s.submit(np.zeros((1, 2), np.float32))  # occupies dispatch
            deadline = time.perf_counter() + 5.0
            while (s.snapshot()["queue_depth"] > 0
                   and time.perf_counter() < deadline):
                time.sleep(0.005)  # wait for the dispatcher to pick it up
            s.submit(np.zeros((1, 2), np.float32))  # fills the queue
            with pytest.raises(ShedError) as ei:
                s.submit(np.zeros((1, 2), np.float32))
            assert ei.value.retry_after_s is not None
        finally:
            gate.set()
            s.shutdown()


# ===========================================================================
# autoscaler mechanics
# ===========================================================================


class TestAutoscalerMechanics:
    def test_boot_spawns_min_replicas(self):
        pool = _pool(min_replicas=2, max_replicas=4)
        try:
            snap = pool.snapshot()
            assert snap["replicas_live"] == 2
            states = {r["state"] for r in snap["replica_servers"]}
            assert states == {"active"}
        finally:
            pool.shutdown()
        assert pool.snapshot()["replicas_live"] == 0

    def test_hysteresis_and_dwell(self):
        now = [0.0]
        pool = _pool(queue_depth_high=4.0, queue_depth_low=0.5,
                     ema_high_s=10.0, ema_low_s=9.0, min_dwell_s=5.0,
                     clock=lambda: now[0])
        try:
            # in-band signals: no action even past the dwell
            assert pool.evaluate(now=10.0) is None
            # force the out-band (and sink the low band so the idle
            # pool cannot legally scale in) — verify dwell-gated out
            pool.queue_depth_high = -1.0
            pool.queue_depth_low = -2.0
            assert pool.evaluate(now=11.0) == "out"
            assert pool.storm_guard_active(now=12.0)
            assert pool.evaluate(now=12.0) is None  # storm guard holds
            assert pool.evaluate(now=17.0) == "out"
            assert pool.snapshot(now=17.0)["replicas_live"] == 3
            assert pool.evaluate(now=30.0) is None  # at max_replicas
            # back in-band: scale-in drains the youngest, one per dwell
            pool.queue_depth_high = 4.0
            pool.queue_depth_low = 0.5
            assert pool.evaluate(now=40.0) == "in"
            assert pool.snapshot(now=40.0)["replicas_live"] == 2
            events = [(e["direction"], e["reason"])
                      for e in pool.snapshot(now=40.0)["events"]]
            assert ("out", "queue_depth") in events
            assert ("in", "idle") in events
            gauge = _counter("dl4j_tpu_fleet_replicas")
            assert list(gauge.values()) == [2.0]
        finally:
            pool.shutdown()

    def test_scale_in_eviction_is_planned_and_silent(self):
        now = [0.0]
        pool = _pool(min_replicas=1, max_replicas=2,
                     queue_depth_high=-1.0, clock=lambda: now[0])
        try:
            assert pool.evaluate(now=1.0) == "out"
            young = max(pool.snapshot(now=1.0)["replica_servers"],
                        key=lambda r: r["name"])
            pool.queue_depth_high = 1e9
            with warnings.catch_warnings():
                warnings.simplefilter("error")  # a planned drain warns nobody
                assert pool.evaluate(now=2.0) == "in"
            info = pool.membership.get(young["replica_id"])
            assert info.state is WorkerState.EVICTED
            assert info.evict_reason == "scale_in"
        finally:
            pool.shutdown()

    def test_spawn_failure_episode_one_bundle_and_backoff(
            self, monkeypatch, tmp_path):
        trace_mod.configure(enabled=True)  # flight dumps are gated
        monkeypatch.setenv("DL4J_TPU_CHAOS", "replica_spawn@2:3")
        chaos.reset_fault_points()
        now = [0.0]
        pool = _pool(min_replicas=1, max_replicas=3,
                     queue_depth_high=-1.0,
                     spawn_backoff_base_s=0.5, spawn_backoff_cap_s=2.0,
                     clock=lambda: now[0])
        try:
            assert pool.snapshot(now=0.0)["replicas_live"] == 1
            # hit 2: the scale-out spawn fails and opens the episode
            assert pool.evaluate(now=1.0) is None
            spawn = pool.snapshot(now=1.0)["spawn"]
            assert spawn["episode_open"] and spawn["failures"] == 1
            assert 0.0 < spawn["retry_in_s"] <= 2.0
            assert len(_bundles(tmp_path, "replica_spawn")) == 1
            # inside the backoff window the pool refuses to act
            assert pool.evaluate(now=1.0) is None
            # hit 3: the retry fails too — episode EXTENDS, no new bundle
            assert pool.evaluate(now=5.0) is None
            assert pool.snapshot(now=5.0)["spawn"]["failures"] == 2
            assert len(_bundles(tmp_path, "replica_spawn")) == 1
            # schedule exhausted: the next retry lands and closes it
            assert pool.evaluate(now=10.0) == "out"
            snap = pool.snapshot(now=10.0)
            assert snap["replicas_live"] == 2
            assert not snap["spawn"]["episode_open"]
            events = _counter("dl4j_tpu_fleet_scale_events_total")
            assert events.get("direction=out,reason=spawn_retry") == 1.0
        finally:
            pool.shutdown()

    def test_fleet_section_aggregates_live_pools(self):
        import gc

        gc.collect()  # drop earlier tests' pools from the WeakSet
        pool = _pool(min_replicas=1)
        try:
            sec = fleet_section()
            assert sec is not None
            assert sec["replicas"] >= 1
            assert isinstance(sec["tenant_slo_firing"], list)
        finally:
            pool.shutdown()
        gc.collect()
        assert fleet_section() is None


# ===========================================================================
# acceptance arc 1: 2x load step -> scale-out, zero cold compiles,
# no availability burn episode
# ===========================================================================


class TestLoadStepArc:
    def test_scale_out_with_zero_cold_compiles_and_no_burn(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.telemetry import introspect

        trace_mod.configure(enabled=True)
        watcher = introspect.watcher()
        cache = str(tmp_path / "warmcache")
        fwd = jax.jit(lambda v: jnp.tanh(v * 2.0))

        def dispatch(xp):
            time.sleep(0.002)  # makes one replica saturable
            return np.asarray(fwd(jnp.asarray(xp)))

        reg = ModelRegistry(warm_cache_dir=cache)
        router = Router(reg)
        pool = None
        try:
            reg.register("m", dispatch=dispatch, batch_limit=8,
                         buckets=BucketSpec(8, sizes=(1, 8)),
                         breaker=CircuitBreaker(failure_threshold=1000),
                         wait_ms=0.5)
            # first boot pays the compiles and records the manifest
            reg.warm("m", example=np.ones((1, 3), np.float32))

            pool = Autoscaler.for_model(
                reg, "m", min_replicas=1, max_replicas=3,
                queue_depth_high=3.0, queue_depth_low=0.5,
                ema_high_s=10.0, ema_low_s=0.0, min_dwell_s=0.0)
            router.attach_autoscaler("m", pool)
            cold_before = watcher.cold_compile_count()

            stop = threading.Event()
            errors = []

            def client(k):
                x = np.ones((1, 3), np.float32)
                while not stop.is_set():
                    try:
                        router.output("m", x, deadline_s=5.0)
                    except ServingError as e:
                        errors.append(e)

            # 16 closed-loop clients >> one replica's capacity: the
            # offered-load step
            cts = [threading.Thread(target=client, args=(k,),
                                    daemon=True, name=f"load-{k}")
                   for k in range(16)]
            for t in cts:
                t.start()
            deadline = time.perf_counter() + 10.0
            scaled = False
            while time.perf_counter() < deadline:
                router.evaluate()  # the pull cadence ticks the pool too
                slo_mod.tick()
                if pool.snapshot()["replicas_live"] >= 2:
                    scaled = True
                    break
                time.sleep(0.01)
            stop.set()
            for t in cts:
                t.join(5.0)
            slo_mod.tick()

            assert scaled, "the load step must scale the pool out"
            assert watcher.cold_compile_count() == cold_before, \
                "scale-out must warm from the cache, never compile"
            assert not errors, f"load-step arc shed requests: {errors[:3]}"
            eng = slo_mod.engine()
            episodes = eng.episode_counts() if eng is not None else {}
            assert episodes.get("serving_availability", 0) == 0, \
                "scale-out must not burn the availability SLO"
            events = _counter("dl4j_tpu_fleet_scale_events_total")
            assert sum(v for k, v in events.items()
                       if "direction=out" in k) >= 1.0
        finally:
            if pool is not None:
                pool.shutdown()
            reg.shutdown()
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 1.0)


# ===========================================================================
# acceptance arc 2: noisy tenant bursts, quiet tenant stays flat
# ===========================================================================


class TestNoisyTenantArc:
    def test_tenant_burst_sheds_only_the_noisy_tenant(self, monkeypatch):
        # the noisy tenant's admissions are the ODD hits (the arc below
        # alternates noisy, quiet, noisy, quiet ...): chaos amplifies
        # exactly those admissions' token cost by BURST_FACTOR
        n_rounds = 40
        schedule = ":".join(str(2 * i + 1) for i in range(n_rounds))
        monkeypatch.setenv("DL4J_TPU_CHAOS", f"tenant_burst@{schedule}")
        chaos.reset_fault_points()

        tenancy = TenancyController()
        # noisy's quota covers its UN-amplified load (~n_rounds rows);
        # at 10x amplified cost the bucket drains almost immediately
        tenancy.add_tenant("noisy", rate=200.0, burst=20.0)
        tenancy.add_tenant("quiet", rate=1e9, burst=1e9)
        s = _server(tenancy=tenancy, queue_limit=64)
        try:
            noisy_shed = 0
            quiet_lat = []
            x = np.ones((1, 2), np.float32)
            for _ in range(n_rounds):
                try:
                    s.output(x, tenant="noisy")
                except TenantQuotaError as e:
                    assert e.tenant == "noisy"
                    assert e.retry_after_s is not None
                    noisy_shed += 1
                t0 = time.perf_counter()
                s.output(x, tenant="quiet")  # must never raise
                quiet_lat.append(time.perf_counter() - t0)

            # the burst overwhelmed noisy's own bucket...
            assert noisy_shed >= n_rounds // 2
            sheds = _counter("dl4j_tpu_tenant_shed_total")
            assert sheds.get("reason=quota,tenant=noisy") == noisy_shed
            # ...while the quiet tenant shed NOTHING and stayed fast
            assert not any("tenant=quiet" in k for k in sheds)
            reqs = _counter("dl4j_tpu_tenant_requests_total")
            assert reqs.get("outcome=ok,tenant=quiet") == float(n_rounds)
            quiet_lat.sort()
            p99 = quiet_lat[int(len(quiet_lat) * 0.99) - 1]
            assert p99 < 0.25, f"quiet tenant p99 {p99:.3f}s not flat"
            # per-tenant SLO slices see the same story
            snap = tenancy.snapshot()["tenants"]
            assert snap["noisy"]["shed"] == noisy_shed
            assert snap["quiet"]["shed"] == 0
        finally:
            s.shutdown()

    def test_burst_factor_amplifies_admission_cost(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_CHAOS", "tenant_burst@1")
        chaos.reset_fault_points()
        ctrl = TenancyController(clock=lambda: 0.0)
        ctrl.add_tenant("t", rate=1.0, burst=float(BURST_FACTOR) - 1)
        # one row at 10x cost exceeds what a burst of 9 can EVER hold:
        # the full bucket admits it once, draining to zero ...
        assert ctrl.admit("t") == "t"
        assert ctrl._buckets["t"].tokens == 0.0
        # ... so the tenant's own next (un-amplified) row sheds
        with pytest.raises(TenantQuotaError):
            ctrl.admit("t")
        inj = _counter("dl4j_tpu_chaos_injections_total")
        assert any("tenant_burst" in k for k in inj)


# ===========================================================================
# acceptance arc 3: replica crash mid-dispatch — typed, requeued
# ===========================================================================


class TestReplicaCrashArc:
    def test_crash_evicts_requeues_and_resolves_typed(self, tmp_path):
        trace_mod.configure(enabled=True)  # eviction bundle is gated
        bombs = {}

        def make(name, tenancy):
            flag = threading.Event()
            bombs[name] = flag

            def dispatch(xp):
                if flag.is_set():
                    raise SystemExit("replica died")  # escapes Exception
                return np.asarray(xp, dtype=np.float32)

            return _server(dispatch=dispatch, name=name, tenancy=tenancy,
                           batch_limit=1, buckets=BucketSpec(1, sizes=(1,)),
                           wait_ms=0.0)

        pool = _pool(factory=make, min_replicas=2, max_replicas=3)
        try:
            assert pool.snapshot()["replicas_live"] == 2
            x = np.ones((1, 2), np.float32)
            assert pool.output(x).shape == (1, 2)
            # arm ONE replica's bomb: its next dispatch kills the
            # dispatcher thread itself
            victim_id = pool.snapshot()["replica_servers"][0]["replica_id"]
            for rid, flag in bombs.items():
                if rid == victim_id:
                    flag.set()
            # hammer until the victim is hit: every call must resolve
            # with a result (requeued onto the survivor) — the caller
            # NEVER sees DispatcherCrashedError. Round-robin over two
            # replicas guarantees the victim dispatches within 8 calls.
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                for _ in range(8):
                    out = pool.output(x)
                    assert out.shape == (1, 2)
                pool.evaluate()
            assert any("evicted" in str(w.message) for w in caught), \
                "a crash eviction is an operator-visible event"
            info = pool.membership.get(victim_id)
            assert info is not None and info.state is WorkerState.EVICTED
            assert info.evict_reason == "crash"
            assert _bundles(tmp_path, "eviction")
            events = _counter("dl4j_tpu_fleet_scale_events_total")
            assert events.get("direction=in,reason=crash", 0) >= 1.0
            # min_replicas heals the pool on the next ticks
            deadline = time.perf_counter() + 5.0
            while (pool.snapshot()["replicas_live"] < 2
                   and time.perf_counter() < deadline):
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    pool.evaluate()
                time.sleep(0.01)
            assert pool.snapshot()["replicas_live"] >= 2
            assert pool.output(x).shape == (1, 2)
        finally:
            pool.shutdown()

    def test_no_live_replica_raises_typed(self):
        pool = _pool(min_replicas=1)
        pool.shutdown()
        with pytest.raises(ServingError):
            pool.output(np.ones((1, 2), np.float32))

    def test_crashed_replica_queue_drains_typed(self):
        def bomb(xp):
            raise SystemExit("dead on arrival")

        s = _server(dispatch=bomb, batch_limit=1,
                    buckets=BucketSpec(1, sizes=(1,)), wait_ms=0.0)
        with pytest.raises(DispatcherCrashedError):
            s.output(np.ones((1, 2), np.float32))
        assert s.crashed
        s.shutdown()


# ===========================================================================
# /fleet endpoint, /healthz merge, serve fleet CLI
# ===========================================================================


class TestFleetSurfaces:
    def test_fleet_endpoint_and_healthz_merge(self):
        import gc

        from deeplearning4j_tpu.ui.server import UIServer

        gc.collect()  # drop earlier tests' pools from the WeakSet
        pool = _pool(min_replicas=1)
        srv = None
        try:
            srv = UIServer(port=0)
            doc = json.loads(urllib.request.urlopen(
                srv.url() + "/fleet").read())
            assert doc["replicas"] >= 1
            assert doc["pools"][0]["name"] == "fleet"
            health = json.loads(urllib.request.urlopen(
                srv.url() + "/healthz").read())
            assert health["fleet"]["replicas"] >= 1
        finally:
            if srv is not None:
                srv.stop()
            pool.shutdown()

    def test_fleet_endpoint_404_without_pool(self):
        import gc

        from deeplearning4j_tpu.ui.server import UIServer

        gc.collect()
        srv = UIServer(port=0)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.url() + "/fleet")
            assert ei.value.code == 404
        finally:
            srv.stop()

    def test_serve_fleet_cli_exit_codes(self, capsys):
        import gc

        from deeplearning4j_tpu import cli
        from deeplearning4j_tpu.ui.server import UIServer

        gc.collect()
        now = [0.0]
        pool = _pool(min_replicas=1, min_dwell_s=3600.0,
                     clock=lambda: now[0])
        srv = None
        try:
            srv = UIServer(port=0)
            # boot counted as a scale event: inside the dwell the storm
            # guard is up — the pager-visible state, exit 2
            assert cli.main(["serve", "fleet", "--url", srv.url()]) == 2
            assert "storm guard" in capsys.readouterr().out
            now[0] = 7200.0  # dwell long past: healthy table, exit 0
            assert cli.main(["serve", "fleet", "--url", srv.url()]) == 0
            out = capsys.readouterr().out
            assert "fleet" in out and "replicas=1" in out
            assert cli.main(["serve", "fleet", "--url", srv.url(),
                             "--json"]) == 0
            assert json.loads(capsys.readouterr().out)["replicas"] == 1
        finally:
            if srv is not None:
                srv.stop()
            pool.shutdown()
        # no pool in the scraped process -> exit 1
        gc.collect()
        srv2 = UIServer(port=0)
        try:
            assert cli.main(["serve", "fleet", "--url", srv2.url()]) == 1
        finally:
            srv2.stop()
        assert cli.main(["serve", "fleet",
                         "--url", "http://127.0.0.1:1"]) == 1

    def test_router_snapshot_and_rollout_exclusivity(self):
        reg = ModelRegistry()
        pool = None
        try:
            reg.register("m", dispatch=_echo, batch_limit=8,
                         buckets=BucketSpec(8, sizes=(1, 8)))
            reg.register("m", dispatch=_echo, version="v2", stable=False,
                         batch_limit=8, buckets=BucketSpec(8, sizes=(1, 8)))
            router = Router(reg)
            pool = Autoscaler.for_model(reg, "m", min_replicas=1,
                                        min_dwell_s=0.0)
            router.attach_autoscaler("m", pool)
            out = router.output("m", np.ones((1, 2), np.float32),
                                tenant="acme")
            assert out.shape == (1, 2)
            assert router.snapshot()["fleets"]["m"]["replicas_live"] == 1
            with pytest.raises(ValueError):
                router.start_rollout("m", "v2")
            router.detach_autoscaler("m")
            router.start_rollout("m", "v2", stages=(1.0,), min_requests=1)
            with pytest.raises(ValueError):
                router.attach_autoscaler("m", pool)
        finally:
            if pool is not None:
                pool.shutdown()
            reg.shutdown()
