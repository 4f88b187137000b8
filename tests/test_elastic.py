"""Elastic membership runtime (distributed/membership.py + the masters).

The chaos matrix: for each fault arc in {host_loss, heartbeat_drop,
straggler-evict, rejoin} x {ParameterAveragingTrainingMaster,
SharedTrainingMaster}, the run COMPLETES, the final params match an
uninterrupted same-seed run, and
``dl4j_tpu_membership_transitions_total{event}`` counts the arc exactly.
Plus the acceptance arc (ISSUE 7): one ``DL4J_TPU_CHAOS=host_loss@2,
rejoin@1`` run proving lose-host -> rebalance -> rejoin -> converge with a
flight bundle for the eviction and a silent stall watchdog; and the
satellites that ride along (decorrelated retry jitter, chaos silent
faults + parse-cache reset, streaming graceful degradation).
"""
import glob
import json
import os
import threading
import warnings as warnings_mod

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.distributed import (
    ElasticTrainer,
    MembershipRegistry,
    ParameterAveragingTrainingMaster,
    SharedTrainingMaster,
    WorkerState,
)
from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn import updaters
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import Dense, Output
from deeplearning4j_tpu.resilience import chaos
from deeplearning4j_tpu.resilience.retry import (
    decorrelated_backoff,
    retry_call,
    seed_jitter,
)
from deeplearning4j_tpu.telemetry import health as health_mod
from deeplearning4j_tpu.telemetry import metrics as metrics_mod
from deeplearning4j_tpu.telemetry import trace as trace_mod

_GATES = (
    "DL4J_TPU_TELEMETRY", "DL4J_TPU_CHAOS", "DL4J_TPU_HEARTBEAT_TIMEOUT",
    "DL4J_TPU_EVICT_SKEW_RATIO", "DL4J_TPU_EVICT_SKEW_SPLITS",
    "DL4J_TPU_REJOIN_BACKOFF", "DL4J_TPU_RETRY_JITTER",
    "DL4J_TPU_RETRY_BACKOFF", "DL4J_TPU_STALL_TIMEOUT",
    "DL4J_TPU_STRAGGLER_RATIO", "DL4J_TPU_STREAM_GRACE",
)


@pytest.fixture(autouse=True)
def _clean_elastic(monkeypatch, tmp_path):
    """Gate-off start, tmp flight dir, zeroed metrics/tracer, re-armed
    chaos counters + seeded jitter around every case."""
    for var in _GATES:
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("DL4J_TPU_FLIGHT_DIR", str(tmp_path / "flight"))
    # fast rejoin deadlines: compiled splits can finish in milliseconds,
    # and a rejoin must land within the test's barrier budget
    monkeypatch.setenv("DL4J_TPU_REJOIN_BACKOFF", "0.005")
    trace_mod.configure(enabled=None)
    trace_mod.tracer().clear()
    metrics_mod.registry().reset()
    chaos.reset_fault_points()
    health_mod.reset_for_tests()
    seed_jitter(1234)
    yield
    trace_mod.configure(enabled=None)
    trace_mod.tracer().clear()
    metrics_mod.registry().reset()
    chaos.reset_fault_points()
    health_mod.reset_for_tests()
    seed_jitter(None)


def _net(seed=1):
    conf = NeuralNetConfiguration(
        seed=seed, updater=updaters.Adam(learning_rate=5e-3),
    ).list([
        Dense(n_out=16, activation="relu"),
        Output(n_out=3, loss="mcxent"),
    ]).set_input_type(it.feed_forward(4))
    return MultiLayerNetwork(conf).init()


def _data(n=48):
    rng = np.random.default_rng(12345)
    x = rng.standard_normal((n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return DataSet(x, y)


_DS = _data()


def _transition_deltas(fn):
    """Run `fn` and return (result, {event: count delta}) over
    dl4j_tpu_membership_transitions_total."""
    cnt = metrics_mod.registry().get("dl4j_tpu_membership_transitions_total")
    before = dict(cnt.snapshot() or {})
    with warnings_mod.catch_warnings():
        warnings_mod.simplefilter("ignore")
        out = fn()
    after = cnt.snapshot()
    return out, {k.split("=", 1)[1]: after[k] - before.get(k, 0.0)
                 for k in after if after[k] != before.get(k, 0.0)}


def _evict_events(deltas):
    return {k: v for k, v in deltas.items() if k.startswith("evict_")}


def _assert_params_close(a, b, atol):
    import jax.tree_util as tu

    for p, q in zip(tu.tree_leaves(a.params), tu.tree_leaves(b.params)):
        np.testing.assert_allclose(np.asarray(p), np.asarray(q), atol=atol,
                                   rtol=0)


def _run_pam(rounds=3, num_workers=2, batch=8, after_round=None):
    net = _net()
    master = ParameterAveragingTrainingMaster(
        num_workers=num_workers, batches_per_worker=1)
    for r in range(rounds):
        master.execute_training(net, ListDataSetIterator(_DS, batch=batch))
        if after_round is not None:
            after_round(r, master)
    return net, master


def _run_stm(rounds=5, batch=16, after_round=None):
    import time

    net = _net()
    master = SharedTrainingMaster()
    for r in range(rounds):
        master.execute_training(net, ListDataSetIterator(_DS, batch=batch))
        if after_round is not None:
            after_round(r, master)
        time.sleep(0.03)  # compiled rounds are ~ms; let backoffs elapse
    return net, master


# ===========================================================================
# membership registry unit arcs
# ===========================================================================


class TestMembershipRegistry:
    def test_state_machine_and_generations(self):
        clock = [0.0]
        reg = MembershipRegistry(heartbeat_timeout=1.0,
                                 clock=lambda: clock[0])
        for w in range(3):
            reg.register(w)
        assert reg.active_count() == 3 and reg.generation == 3
        # silence one worker past the timeout: suspect, then evict
        reg.heartbeat(0), reg.heartbeat(1)
        clock[0] = 2.0
        reg.heartbeat(0), reg.heartbeat(1)
        assert reg.suspect_silent() == []  # first pass: suspect only
        assert reg.get(2).state is WorkerState.SUSPECT
        assert reg.suspect_silent() == [2]  # second pass: evicted
        assert reg.get(2).state is WorkerState.EVICTED
        assert reg.get(2).evict_reason == "heartbeat"
        assert not reg.is_active(2) and reg.active_count() == 2
        assert reg.get(2).drain.is_set()
        gen_after_evict = reg.generation
        assert gen_after_evict == 4
        # a beat rescues a suspect before the second pass
        clock[0] = 4.0
        assert reg.suspect_silent() == []
        assert reg.get(1).state is WorkerState.SUSPECT
        reg.heartbeat(1)
        assert reg.get(1).state is WorkerState.ACTIVE
        reg.heartbeat(0)

    def test_exception_detection_reasons(self):
        reg = MembershipRegistry()
        reg.register(0), reg.register(1)
        reg.report_failure(0, chaos.ChaosError("host gone"))  # IOError
        reg.report_failure(1, ValueError("user bug"))
        assert reg.get(0).evict_reason == "host_loss"
        assert reg.get(1).evict_reason == "exception"
        # transient host loss is scheduled for rejoin; app errors are not
        assert reg.get(0).rejoin_not_before is not None
        assert reg.get(1).rejoin_not_before is None

    def test_rejoin_barrier_chaos_and_backoff(self, monkeypatch):
        clock = [0.0]
        reg = MembershipRegistry(clock=lambda: clock[0])
        reg.register(0), reg.register(1)
        reg.report_failure(1, chaos.ChaosError("gone"))
        monkeypatch.setenv("DL4J_TPU_CHAOS", "rejoin@1")
        chaos.reset_fault_points()
        clock[0] = 10.0  # backoff elapsed: candidate is due
        assert reg.barrier(splits_done=3) == []  # first barrier FAILS
        info = reg.get(1)
        assert info.state is WorkerState.EVICTED
        assert info.rejoin_attempts == 1
        assert info.rejoin_not_before > 10.0  # backed off again
        clock[0] = 100.0
        assert reg.barrier(splits_done=5) == [1]  # next barrier admits
        assert info.state is WorkerState.ACTIVE
        assert info.resume_split == 5
        assert reg.is_active(1)

    def test_barrier_agrees_on_manifest_resume_split(self, tmp_path):
        from deeplearning4j_tpu.distributed.elastic import CheckpointManager

        clock = [0.0]
        reg = MembershipRegistry(clock=lambda: clock[0])
        reg.register(0)
        reg.report_failure(0, chaos.ChaosError("gone"))
        cm = CheckpointManager(str(tmp_path), keep=2)
        cm.save(_net(), 4, extra={"splits_done": 4})
        clock[0] = 10.0
        assert reg.barrier(splits_done=99, checkpoint_manager=cm) == [0]
        # the MANIFEST (PR 2 atomic machinery) wins over in-memory state
        assert reg.get(0).resume_split == 4

    def test_straggler_drain_consecutive_splits(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_EVICT_SKEW_RATIO", "2.0")
        monkeypatch.setenv("DL4J_TPU_EVICT_SKEW_SPLITS", "2")
        reg = MembershipRegistry()
        for w in range(4):
            reg.register(w)
        slow = {0: 0.1, 1: 0.1, 2: 0.1, 3: 1.0}
        with warnings_mod.catch_warnings():
            warnings_mod.simplefilter("ignore")
            report = reg.observe_split_durations(slow)
            assert report[3] > 2.0 and reg.is_active(3)  # 1st: counted
            # a fast split in between RESETS the consecutive counter
            reg.observe_split_durations({w: 0.1 for w in range(4)})
            reg.observe_split_durations(slow)
            assert reg.is_active(3)
            reg.observe_split_durations(slow)  # 2nd consecutive: drained
        assert not reg.is_active(3)
        assert reg.get(3).evict_reason == "straggler"
        # drained stragglers are NOT auto-rejoined
        assert reg.get(3).rejoin_not_before is None

    def test_barrier_admission_failure_backs_off_not_strands(self):
        clock = [0.0]
        reg = MembershipRegistry(clock=lambda: clock[0])
        reg.register(0)
        reg.report_failure(0, chaos.ChaosError("gone"))

        class FlakyCkpt:
            def manifests(self):
                raise OSError("checkpoint dir unreachable")

        clock[0] = 10.0
        with pytest.warns(UserWarning, match="backing off"):
            assert reg.barrier(3, checkpoint_manager=FlakyCkpt()) == []
        info = reg.get(0)
        # backed off EVICTED (retryable at a later barrier), not stranded
        # in REJOINING — and the run itself was not killed
        assert info.state is WorkerState.EVICTED
        assert info.rejoin_attempts == 1
        clock[0] = 100.0
        assert reg.barrier(5) == [0]

    def test_exception_evictions_reset_on_next_fit(self):
        """A bad-data run that evicts every worker must not brick the
        master: the next fit() re-registers exception-evicted workers
        (the error was scoped to the data, not the hosts)."""
        bad = DataSet(np.full((16, 4), np.nan, np.float32),
                      np.eye(3, dtype=np.float32)[[0] * 16])
        net = _net()
        master = ParameterAveragingTrainingMaster(num_workers=2,
                                                  batches_per_worker=1)

        class Boom(Exception):
            pass

        orig = net.clone

        def bad_clone():
            m = orig()

            def explode(ds):
                raise Boom()

            m._dispatch_step = explode
            return m

        net.clone = bad_clone
        with warnings_mod.catch_warnings():
            warnings_mod.simplefilter("ignore")
            with pytest.raises(Boom):
                master.execute_training(net,
                                        ListDataSetIterator(bad, batch=8))
            assert master.membership.active_count() == 0
            net.clone = orig
            master.execute_training(net, ListDataSetIterator(_DS, batch=8))
        assert sorted(master.membership.active_ids()) == [0, 1]
        assert np.isfinite(net.score_)

    def test_multi_controller_event_routing(self):
        a = MembershipRegistry()
        a.register(0)
        a.report_failure(0, chaos.ChaosError("gone"))
        events = a.drain_pending_events()
        assert [e["event"] for e in events] == ["join", "evict_host_loss"]
        assert a.drain_pending_events() == []  # drained
        b = MembershipRegistry()
        for evt in events:
            b.apply_remote_event(evt, origin=1)
        info = b.get("p1:0")
        assert info is not None and info.state is WorkerState.EVICTED
        # remote-applied transitions are NOT re-queued (no ping-pong)
        assert b.drain_pending_events() == []


# ===========================================================================
# chaos matrix: ParameterAveragingTrainingMaster
# ===========================================================================


class TestChaosMatrixParameterAveraging:
    def test_host_loss_evicts_rebalances_and_matches(self, monkeypatch):
        ref, _ = _run_pam()
        monkeypatch.setenv("DL4J_TPU_CHAOS", "host_loss@2")
        chaos.reset_fault_points()
        (got, master), deltas = _transition_deltas(lambda: _run_pam())
        assert _evict_events(deltas) == {"evict_host_loss": 1.0}
        assert deltas.get("rejoin") == 1.0  # auto-rejoined at a barrier
        assert sorted(master.membership.active_ids()) == [0, 1]
        # shards are the unit of work: the rebalanced run IS the
        # fault-free run, not merely close to it
        _assert_params_close(ref, got, atol=1e-6)
        assert got.iteration == ref.iteration

    def test_heartbeat_drop_detected_not_crashed(self, monkeypatch):
        ref, _ = _run_pam()
        monkeypatch.setenv("DL4J_TPU_CHAOS", "heartbeat_drop@1")
        # generous window: first-batch jit compile must not read as death
        monkeypatch.setenv("DL4J_TPU_HEARTBEAT_TIMEOUT", "2.0")
        chaos.reset_fault_points()
        (got, master), deltas = _transition_deltas(lambda: _run_pam())
        assert _evict_events(deltas) == {"evict_heartbeat": 1.0}
        assert deltas.get("suspect") == 1.0  # went through SUSPECT first
        assert deltas.get("rejoin") == 1.0
        _assert_params_close(ref, got, atol=1e-6)
        # the silent-injection is counted distinctly from raising faults
        inj = metrics_mod.registry().get("dl4j_tpu_chaos_injections_total")
        assert inj.snapshot().get("point=heartbeat_drop.silent") == 1.0

    def test_straggler_evict_drains_and_matches(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_EVICT_SKEW_RATIO", "4.0")
        monkeypatch.setenv("DL4J_TPU_EVICT_SKEW_SPLITS", "2")

        def drain(r, master):
            if r == 0:
                # two consecutive slow windows for worker 3 — the drive an
                # operator's skew gauges would deliver
                slow = {0: 0.1, 1: 0.1, 2: 0.1, 3: 1.0}
                with warnings_mod.catch_warnings():
                    warnings_mod.simplefilter("ignore")
                    master.membership.observe_split_durations(slow)
                    master.membership.observe_split_durations(slow)

        ref, _ = _run_pam(num_workers=4)
        (got, master), deltas = _transition_deltas(
            lambda: _run_pam(num_workers=4, after_round=drain))
        assert _evict_events(deltas) == {"evict_straggler": 1.0}
        assert "rejoin" not in deltas  # drained means drained
        assert sorted(master.membership.active_ids()) == [0, 1, 2]
        # eviction changes EXECUTORS, never shards: params stay exact
        _assert_params_close(ref, got, atol=1e-6)


# ===========================================================================
# chaos matrix: SharedTrainingMaster
# ===========================================================================


class TestChaosMatrixSharedTraining:
    def test_host_loss_degrades_mesh_and_rejoins(self, monkeypatch):
        ref, _ = _run_stm()
        monkeypatch.setenv("DL4J_TPU_CHAOS", "host_loss@1,rejoin@1")
        chaos.reset_fault_points()
        (got, master), deltas = _transition_deltas(lambda: _run_stm())
        assert _evict_events(deltas) == {"evict_host_loss": 1.0}
        assert deltas.get("rejoin_failed") == 1.0  # chaos hit the barrier
        assert deltas.get("rejoin") == 1.0         # backoff, next barrier
        assert master.membership.active_count() == \
            master.membership.snapshot()["workers"].__len__()
        # refit-from-snapshot on the divisor-degraded mesh: same global
        # batches, even shards — reduction-order noise only
        _assert_params_close(ref, got, atol=1e-6)

    def test_heartbeat_drop_lane_detected(self, monkeypatch):
        ref, _ = _run_stm()
        monkeypatch.setenv("DL4J_TPU_CHAOS", "heartbeat_drop@1")
        chaos.reset_fault_points()
        (got, master), deltas = _transition_deltas(lambda: _run_stm())
        assert _evict_events(deltas) == {"evict_heartbeat": 1.0}
        assert deltas.get("suspect") == 1.0
        assert deltas.get("rejoin") == 1.0
        _assert_params_close(ref, got, atol=1e-6)

    def test_straggler_evict_lane_drained(self, monkeypatch):
        import jax

        n_lanes = max(1, jax.local_device_count())
        if n_lanes < 3:
            pytest.skip("straggler ratios need >= 3 lanes")
        monkeypatch.setenv("DL4J_TPU_EVICT_SKEW_RATIO", "4.0")
        monkeypatch.setenv("DL4J_TPU_EVICT_SKEW_SPLITS", "2")

        def drain(r, master):
            if r == 0:
                slow = {w: 0.1 for w in range(n_lanes)}
                slow[n_lanes - 1] = 1.0
                with warnings_mod.catch_warnings():
                    warnings_mod.simplefilter("ignore")
                    master.membership.observe_split_durations(slow)
                    master.membership.observe_split_durations(slow)

        ref, _ = _run_stm()
        (got, master), deltas = _transition_deltas(
            lambda: _run_stm(after_round=drain))
        assert _evict_events(deltas) == {"evict_straggler": 1.0}
        assert "rejoin" not in deltas
        assert not master.membership.is_active(n_lanes - 1)
        # the drained lane actually LEFT the mesh (divisor-degraded axis)
        assert dict(master._wrapper.mesh.shape)["data"] < n_lanes
        _assert_params_close(ref, got, atol=1e-6)


# ===========================================================================
# the acceptance arc (ISSUE 7): K -> K-1 -> K under one chaos value
# ===========================================================================


class TestAcceptanceArc:
    def test_lose_host_rebalance_rejoin_converge(self, monkeypatch,
                                                 tmp_path):
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        monkeypatch.setenv("DL4J_TPU_STALL_TIMEOUT", "60")
        flight_dir = str(tmp_path / "flight")
        monkeypatch.setenv("DL4J_TPU_FLIGHT_DIR", flight_dir)

        def run(ckpt_dir):
            net = _net()
            master = ParameterAveragingTrainingMaster(
                num_workers=2, batches_per_worker=1)
            trainer = ElasticTrainer(master, ckpt_dir, checkpoint_every=1)
            trainer.fit(net, ListDataSetIterator(_DS, batch=8), epochs=2)
            return net, master, trainer

        ref, _, _ = run(str(tmp_path / "ckpt_ref"))
        stalls = metrics_mod.registry().get("dl4j_tpu_stall_detected_total")
        stalls_before = stalls.snapshot()
        monkeypatch.setenv("DL4J_TPU_CHAOS", "host_loss@2,rejoin@1")
        chaos.reset_fault_points()
        (out, deltas) = _transition_deltas(
            lambda: run(str(tmp_path / "ckpt_chaos")))
        got, master, trainer = out
        # exactly ONE eviction and ONE (eventually successful) rejoin
        assert _evict_events(deltas) == {"evict_host_loss": 1.0}
        assert deltas.get("rejoin") == 1.0
        assert deltas.get("rejoin_failed") == 1.0  # the chaos'd barrier
        # K -> K-1 -> K: everyone is back
        assert sorted(master.membership.active_ids()) == [0, 1]
        # ... and the degraded arc CONVERGED ON the fault-free trajectory
        _assert_params_close(ref, got, atol=1e-6)
        assert got.iteration == ref.iteration
        # a flight bundle was written for the eviction
        bundles = glob.glob(os.path.join(flight_dir, "flight_*_eviction.json"))
        assert len(bundles) == 1
        bundle = json.load(open(bundles[0]))
        assert "evicted" in bundle["note"]
        # the rejoin barrier agreed through the atomic manifest
        manifests = trainer.ckpt.manifests()
        assert manifests and "membership_generation" in manifests[-1]
        assert master.membership.get(1).resume_split is not None or \
            master.membership.get(0).resume_split is not None
        # the stall watchdog stayed SILENT: rebalance must not read as a
        # hang
        assert stalls.snapshot() == stalls_before

    def test_elastic_trainer_owns_membership(self, tmp_path):
        master = ParameterAveragingTrainingMaster(num_workers=2)
        trainer = ElasticTrainer(master, str(tmp_path))
        assert master.membership is trainer.membership
        assert master.barrier_checkpoints is trainer.ckpt


# ===========================================================================
# satellites
# ===========================================================================


class TestRetryJitter:
    def test_decorrelated_backoff_bounds_and_seeding(self):
        seed_jitter(7)
        seq1 = []
        prev = 0.1
        for _ in range(8):
            prev = decorrelated_backoff(prev, 0.1, cap=5.0)
            seq1.append(prev)
            assert 0.1 <= prev <= 5.0
        seed_jitter(7)
        seq2 = []
        prev = 0.1
        for _ in range(8):
            prev = decorrelated_backoff(prev, 0.1, cap=5.0)
            seq2.append(prev)
        assert seq1 == seq2  # seedable: chaos arcs replay exactly
        seed_jitter(8)
        prev = 0.1
        assert [decorrelated_backoff(prev, 0.1)] != seq1[:1]

    def test_retry_call_env_jitter_decorrelates(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_RETRY_JITTER", "1")

        def delays_for(seed):
            seed_jitter(seed)
            delays = []

            def fail():
                raise OSError("nope")

            with pytest.raises(OSError):
                retry_call(fail, attempts=4, backoff=0.05,
                           sleep=delays.append)
            return delays

        a, b = delays_for(1), delays_for(2)
        assert len(a) == len(b) == 3
        # two workers that failed together do NOT retry in lockstep
        assert a != b
        assert a == delays_for(1)  # but each is reproducible
        # jitter off (gate cleared): the historical deterministic schedule
        monkeypatch.delenv("DL4J_TPU_RETRY_JITTER")
        assert delays_for(1) == [0.05, 0.1, 0.2]


class TestChaosSatellites:
    def test_silent_fault_counts_distinctly(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_CHAOS", "heartbeat_drop@2")
        chaos.reset_fault_points()
        assert chaos.silent_fault("heartbeat_drop") is False
        assert chaos.silent_fault("heartbeat_drop") is True
        inj = metrics_mod.registry().get("dl4j_tpu_chaos_injections_total")
        snap = inj.snapshot()
        assert snap.get("point=heartbeat_drop.silent") == 1.0
        assert "point=heartbeat_drop" not in snap

    def test_reset_clears_parse_cache(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_CHAOS", "pt@1")
        chaos.reset_fault_points()
        with pytest.raises(chaos.ChaosError):
            chaos.fault_point("pt")
        assert chaos._parse_cache[0] == "pt@1"
        chaos.reset_fault_points()
        # BOTH the counters and the cached parse are re-armed
        assert chaos._parse_cache == (None, {})
        with pytest.raises(chaos.ChaosError):
            chaos.fault_point("pt")


class TestStreamingDegradation:
    def test_publish_to_closed_topic_drops_with_counter(self):
        from deeplearning4j_tpu.distributed.streaming import Topic

        dropped = metrics_mod.registry().get("dl4j_tpu_stream_dropped_total")
        t = Topic("t")
        sub = t.subscribe_queue()
        t.publish(1)
        t.close()
        with pytest.warns(UserWarning, match="closed"):
            t.publish(2)  # no raise: degrade, count, warn once
        with warnings_mod.catch_warnings():
            warnings_mod.simplefilter("error")
            t.publish(3)  # warned ONCE only
        assert dropped.snapshot().get("reason=closed_topic") == 2.0
        assert sub.get(timeout=1) == 1  # pre-close record still delivered

    def test_subscriber_overflow_drops_instead_of_blocking(self,
                                                           monkeypatch):
        from deeplearning4j_tpu.distributed.streaming import Topic

        monkeypatch.setenv("DL4J_TPU_STREAM_GRACE", "0.05")
        dropped = metrics_mod.registry().get("dl4j_tpu_stream_dropped_total")
        before = dict(dropped.snapshot() or {})
        t = Topic("t", capacity=1)
        dead = t.subscribe_queue()  # consumer evicted mid-run: never reads
        live_records = []
        t.subscribe(live_records.append)  # healthy sibling callback
        done = threading.Event()

        def produce():
            t.publish("a")  # fills the dead queue
            with warnings_mod.catch_warnings():
                warnings_mod.simplefilter("ignore")
                t.publish("b")  # must NOT block forever
                t.publish("c")
            done.set()

        prod = threading.Thread(target=produce, daemon=True)
        prod.start()
        assert done.wait(5.0), "producer wedged on a dead subscriber"
        after = dropped.snapshot()
        assert after.get("reason=queue_overflow", 0.0) \
            - before.get("reason=queue_overflow", 0.0) == 2.0
        assert live_records == ["a", "b", "c"]  # siblings unaffected
        assert dead.get(timeout=1) == "a"
