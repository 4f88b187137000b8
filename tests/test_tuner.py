"""Closed-loop self-tuning runtime (ISSUE 18 acceptance): the typed
knob registry (types/ranges/mutability/provenance), the pure signal->
knob rules (fire at threshold, hold inside the hysteresis band,
deterministic), the controller's probation/graduation arc, the SLO-gate
revert (synthetic burn -> every probational knob unwound, exactly ONE
flight bundle per episode), the chaos `tuner_misstep` acceptance arc
with exact decision/revert counts, the engine's epoch-tick closed loop,
the prefetch live knob, the serving bucket re-cut (warm-before-swap,
never a cold compile), the offline sweep, jaxlint JX021, and the knob
snapshots stamped into profile reports and flight bundles. All arcs run
injected clocks — no sleeps."""
import json
import os

import numpy as np
import pytest

from deeplearning4j_tpu.analysis import jaxlint
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import (
    AsyncDataSetIterator,
    ListDataSetIterator,
)
from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn import updaters
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import Dense, Output
from deeplearning4j_tpu.resilience import chaos
from deeplearning4j_tpu.serving.buckets import BucketSpec
from deeplearning4j_tpu.serving.runtime import InferenceServer
from deeplearning4j_tpu.telemetry import flight as flight_mod
from deeplearning4j_tpu.telemetry import health as health_mod
from deeplearning4j_tpu.telemetry import metrics as metrics_mod
from deeplearning4j_tpu.telemetry import slo as slo_mod
from deeplearning4j_tpu.telemetry import trace as trace_mod
from deeplearning4j_tpu.telemetry import tuner as tuner_mod
from deeplearning4j_tpu.telemetry.slo import Selector, SloRule
from deeplearning4j_tpu.tuning import decisions as decisions_mod
from deeplearning4j_tpu.tuning import rules as rules_mod
from deeplearning4j_tpu.util import envflags

WINDOW = rules_mod.WINDOW_KNOB
PREFETCH = rules_mod.PREFETCH_KNOB


@pytest.fixture(autouse=True)
def _clean(monkeypatch, tmp_path):
    """Gate-off start: private journal + flight dirs, zeroed tuner
    singleton/overrides, metrics, tracer, chaos, slo around each case."""
    for var in ("DL4J_TPU_AUTOTUNE", "DL4J_TPU_TELEMETRY",
                "DL4J_TPU_CHAOS", WINDOW, PREFETCH):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("DL4J_TPU_TUNER_DIR", str(tmp_path / "tuner"))
    monkeypatch.setenv("DL4J_TPU_FLIGHT_DIR", str(tmp_path / "flight"))
    trace_mod.configure(enabled=None)
    trace_mod.tracer().clear()
    metrics_mod.registry().reset()
    chaos.reset_fault_points()
    slo_mod.reset_for_tests()
    health_mod.reset_for_tests()
    tuner_mod.reset_for_tests()
    yield
    trace_mod.configure(enabled=None)
    trace_mod.tracer().clear()
    metrics_mod.registry().reset()
    chaos.reset_fault_points()
    slo_mod.reset_for_tests()
    health_mod.reset_for_tests()
    tuner_mod.reset_for_tests()


def _journal():
    return decisions_mod.read_journal()


def _bundles(tmp_path, reason="tuner_revert"):
    d = tmp_path / "flight"
    if not d.is_dir():
        return []
    return sorted(p for p in os.listdir(d) if reason in p)


def _net(seed=1):
    conf = NeuralNetConfiguration(
        seed=seed, updater=updaters.Adam(learning_rate=5e-3),
    ).list([
        Dense(n_out=16, activation="relu"),
        Output(n_out=3, loss="mcxent"),
    ]).set_input_type(it.feed_forward(4))
    return MultiLayerNetwork(conf).init()


def _iris(n=60, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return DataSet(x, y)


# ===========================================================================
# satellite 1: the typed knob registry
# ===========================================================================


class TestKnobRegistry:
    def test_every_knob_declared_once_with_type_and_mutability(self):
        for name, k in envflags.KNOBS.items():
            assert name.startswith("DL4J_TPU_")
            assert k.kind in ("bool", "int", "float", "str")
            assert k.mutability in (envflags.STATIC, envflags.LIVE)
        # the two live-tunable knobs the controller steers
        assert envflags.knob(WINDOW).mutability == envflags.LIVE
        assert envflags.knob(PREFETCH).mutability == envflags.LIVE
        assert envflags.knob("DL4J_TPU_AUTOTUNE").mutability == \
            envflags.STATIC

    def test_override_coerces_and_clamps_to_declared_range(self):
        assert envflags.set_override(WINDOW, 4) == "4"
        assert envflags.int_value(WINDOW, 1) == 4
        # above the declared hi -> clamped, not rejected
        envflags.set_override(WINDOW, 10 ** 6)
        assert envflags.int_value(WINDOW, 1) == envflags.knob(WINDOW).hi
        envflags.set_override(WINDOW, -3)
        assert envflags.int_value(WINDOW, 1) == envflags.knob(WINDOW).lo

    def test_static_knobs_reject_overrides(self):
        with pytest.raises(ValueError):
            envflags.set_override("DL4J_TPU_AUTOTUNE", 1)

    def test_undeclared_knobs_reject_overrides(self):
        with pytest.raises(KeyError):
            envflags.set_override("DL4J_TPU_NOT_A_KNOB", 1)

    def test_provenance_default_env_tuner(self, monkeypatch):
        assert envflags.effective(WINDOW) == ("1", envflags.PROV_DEFAULT)
        monkeypatch.setenv(WINDOW, "2")
        assert envflags.effective(WINDOW) == ("2", envflags.PROV_ENV)
        envflags.set_override(WINDOW, 8)
        # the override overlay outranks the environment for LIVE knobs
        assert envflags.effective(WINDOW) == ("8", envflags.PROV_TUNER)
        assert envflags.int_value(WINDOW, 1) == 8
        envflags.clear_override(WINDOW)
        assert envflags.effective(WINDOW) == ("2", envflags.PROV_ENV)

    def test_describe_flags_undeclared_env_vars(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_TYPO_GATE", "1")
        rows = {r["name"]: r for r in envflags.describe()}
        assert rows["DL4J_TPU_TYPO_GATE"]["declared"] is False
        assert rows[WINDOW]["declared"] is True

    def test_snapshot_is_compact_and_attributed(self, monkeypatch):
        # compact: only non-default knobs appear (the fixture's two
        # tmp-dir env vars are the whole baseline)
        assert set(envflags.snapshot()) == {"DL4J_TPU_TUNER_DIR",
                                            "DL4J_TPU_FLIGHT_DIR"}
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        envflags.set_override(PREFETCH, 8)
        snap = envflags.snapshot()
        assert snap["DL4J_TPU_TELEMETRY"]["provenance"] == \
            envflags.PROV_ENV
        assert snap[PREFETCH] == {"value": "8",
                                  "provenance": envflags.PROV_TUNER}
        assert WINDOW not in snap  # still at default


# ===========================================================================
# satellite 4 (unit arcs): pure rules — threshold, hysteresis, determinism
# ===========================================================================


class TestWindowRule:
    def test_fires_exactly_at_widen_threshold(self):
        at = rules_mod.window_rule(
            {"host_overhead_ms": 35.0, "step_ms": 100.0})
        assert at is not None and at.new == 2 and at.direction == "up"
        below = rules_mod.window_rule(
            {"host_overhead_ms": 34.9, "step_ms": 100.0})
        assert below is None

    def test_holds_inside_hysteresis_band(self):
        envflags.set_override(WINDOW, 4)
        # 0.10 <= share < 0.35: neither widen nor narrow
        for host in (10.0, 20.0, 34.9):
            assert rules_mod.window_rule(
                {"host_overhead_ms": host, "step_ms": 100.0}) is None

    def test_narrows_only_below_release_threshold(self):
        envflags.set_override(WINDOW, 4)
        p = rules_mod.window_rule(
            {"host_overhead_ms": 9.9, "step_ms": 100.0})
        assert p is not None and p.new == 2 and p.direction == "down"
        assert p.reason == "window_host_amortized"

    def test_caps_at_window_max_and_floor_at_one(self):
        envflags.set_override(WINDOW, rules_mod.WINDOW_MAX)
        assert rules_mod.window_rule(
            {"host_overhead_ms": 90.0, "step_ms": 100.0}) is None
        envflags.clear_override(WINDOW)  # K=1
        assert rules_mod.window_rule(
            {"host_overhead_ms": 1.0, "step_ms": 100.0}) is None

    def test_deterministic(self):
        sig = {"host_overhead_ms": 50.0, "step_ms": 100.0}
        a = rules_mod.window_rule(dict(sig))
        b = rules_mod.window_rule(dict(sig))
        assert (a.knob, a.new, a.reason, a.signals) == \
            (b.knob, b.new, b.reason, b.signals)


class TestPrefetchRule:
    def test_deepens_on_input_bound(self):
        p = rules_mod.prefetch_rule({"verdict": "input_bound"})
        assert p is not None and p.new == 8 and p.direction == "up"

    def test_balanced_and_unknown_hold(self):
        assert rules_mod.prefetch_rule({"verdict": "balanced"}) is None
        assert rules_mod.prefetch_rule({"verdict": "unknown"}) is None
        assert rules_mod.prefetch_rule({}) is None

    def test_shallows_on_compute_bound_only_above_default(self):
        assert rules_mod.prefetch_rule(
            {"verdict": "compute_bound"}) is None  # already at default
        envflags.set_override(PREFETCH, 16)
        p = rules_mod.prefetch_rule({"verdict": "compute_bound"})
        assert p is not None and p.new == 8 and p.direction == "down"

    def test_caps_at_prefetch_max(self):
        envflags.set_override(PREFETCH, rules_mod.PREFETCH_MAX)
        assert rules_mod.prefetch_rule({"verdict": "input_bound"}) is None


class TestPlanBuckets:
    def test_holds_below_min_samples(self):
        spec = BucketSpec(32)
        assert rules_mod.plan_buckets([5] * 31, spec) is None

    def test_holds_when_waste_acceptable(self):
        spec = BucketSpec(32)
        # rows of 8 land exactly in the 8-bucket: zero waste
        assert rules_mod.plan_buckets([8] * 64, spec) is None

    def test_recuts_to_observed_quantiles(self):
        spec = BucketSpec(32)
        # rows of 5 pad to 8: waste 0.375 > 0.25 -> snug 5-bucket
        plan = rules_mod.plan_buckets([5] * 64, spec)
        assert plan == [5, 32]  # max_batch invariant kept

    def test_respects_align(self):
        spec = BucketSpec(32, align=4)
        plan = rules_mod.plan_buckets([5] * 64, spec)
        assert plan is not None and all(s % 4 == 0 for s in plan)


class TestPlanFitConfig:
    def test_escalation_order(self):
        gib = 1024 ** 3
        fits = rules_mod.plan_fit_config(4 * gib, 2 * gib, 16 * gib)
        assert (fits["remat"], fits["fsdp"], fits["reason"]) == \
            (False, 1, "fits_plain")
        remat = rules_mod.plan_fit_config(20 * gib, 10 * gib, 16 * gib)
        assert remat["reason"] == "fits_with_remat" and remat["remat"]
        fsdp = rules_mod.plan_fit_config(
            40 * gib, 30 * gib, 16 * gib, fsdp_available=4,
            train_bytes_fsdp=10 * gib)
        assert fsdp["reason"] == "fits_with_fsdp" and fsdp["fsdp"] == 4
        over = rules_mod.plan_fit_config(400 * gib, 300 * gib, 16 * gib)
        assert over["reason"] == "over_budget"

    def test_watermark_scales_predictions(self):
        gib = 1024 ** 3
        # fits plain on paper, but reality ran 2x hot -> plan remat
        plan = rules_mod.plan_fit_config(10 * gib, 5 * gib, 16 * gib,
                                         watermark_ratio=2.0)
        assert plan["reason"] == "fits_with_remat"
        assert plan["watermark_scale"] == 2.0


# ===========================================================================
# controller arcs: probation, graduation, SLO revert (injected clocks)
# ===========================================================================


def _patched_episodes(monkeypatch):
    box = [0]
    monkeypatch.setattr(tuner_mod.Tuner, "_slo_episodes",
                        staticmethod(lambda: box[0]))
    return box


class TestTunerController:
    def test_tick_applies_journals_and_probations(self, monkeypatch):
        _patched_episodes(monkeypatch)
        t = tuner_mod.Tuner(now=lambda: 100.0)
        out = t.tick(signals={"host_overhead_ms": 50.0, "step_ms": 100.0,
                              "verdict": "balanced"}, now=1.0)
        assert len(out) == 1
        assert envflags.effective(WINDOW) == ("2", envflags.PROV_TUNER)
        st = t.status()
        assert st["decisions"] == 1 and st["reverts"] == 0
        assert st["probation"][0]["knob"] == WINDOW
        (entry,) = _journal()
        assert entry["knob"] == WINDOW and entry["applied"] is True
        assert entry["reason"] == "window_host_bound"
        assert entry["signals"]["host_share"] == 0.5
        assert entry["ts"] == 1.0  # the injected clock, not wall time

    def test_probation_graduates_after_clean_ticks(self, monkeypatch):
        _patched_episodes(monkeypatch)
        t = tuner_mod.Tuner(now=lambda: 0.0)
        t.tick(signals={"host_overhead_ms": 50.0, "step_ms": 100.0,
                        "verdict": "balanced"}, now=1.0)
        hold = {"host_overhead_ms": 20.0, "step_ms": 100.0,
                "verdict": "balanced"}
        t.tick(signals=hold, now=2.0)
        assert t.status()["probation"]  # one clean tick: still watched
        t.tick(signals=hold, now=3.0)
        assert t.status()["probation"] == []  # graduated

    def test_burn_reverts_all_probational_newest_first(
            self, monkeypatch, tmp_path):
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        trace_mod.configure(enabled=True)
        episodes = _patched_episodes(monkeypatch)
        t = tuner_mod.Tuner(now=lambda: 0.0)
        t.tick(signals={"host_overhead_ms": 50.0, "step_ms": 100.0,
                        "verdict": "input_bound"}, now=1.0)
        assert envflags.int_value(WINDOW, 1) == 2
        assert envflags.int_value(PREFETCH, 4) == 8
        episodes[0] = 1  # burn opens while both changes are probational
        out = t.tick(signals={}, now=2.0)
        assert len(out) == 2
        assert all(d.reason == "slo_revert" for d in out)
        # newest-first unwind: prefetch (applied second) reverts first
        assert [d.knob for d in out] == [PREFETCH, WINDOW]
        assert envflags.overrides() == {}  # both knobs restored
        assert t.status()["reverts"] == 2
        assert len(_bundles(tmp_path)) == 1  # ONE bundle for the episode

    def test_one_bundle_per_episode_not_per_revert(
            self, monkeypatch, tmp_path):
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        trace_mod.configure(enabled=True)
        episodes = _patched_episodes(monkeypatch)
        t = tuner_mod.Tuner(now=lambda: 0.0)
        widen = {"host_overhead_ms": 50.0, "step_ms": 100.0,
                 "verdict": "balanced"}
        t.tick(signals=widen, now=1.0)
        episodes[0] = 1
        t.tick(signals={}, now=2.0)  # revert + bundle
        assert len(_bundles(tmp_path)) == 1
        # a NEW decision under the same episode count, then a SECOND
        # episode: the second burn gets its own bundle
        t.tick(signals=widen, now=3.0)
        episodes[0] = 2
        t.tick(signals={}, now=4.0)
        assert t.status()["reverts"] == 2
        assert len(_bundles(tmp_path)) == 2

    def test_burn_with_nothing_probational_does_not_bundle(
            self, monkeypatch, tmp_path):
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        trace_mod.configure(enabled=True)
        episodes = _patched_episodes(monkeypatch)
        t = tuner_mod.Tuner(now=lambda: 0.0)
        episodes[0] = 1  # burn, but the tuner changed nothing
        out = t.tick(signals={}, now=1.0)
        assert out == [] and _bundles(tmp_path) == []


# ===========================================================================
# the acceptance arc: chaos-forced misstep -> SLO gate reverts in one tick
# ===========================================================================


class TestChaosMisstepAcceptance:
    def test_misstep_reverted_within_one_tick_exact_counts(
            self, monkeypatch, tmp_path):
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        trace_mod.configure(enabled=True)
        monkeypatch.setenv("DL4J_TPU_AUTOTUNE", "1")
        monkeypatch.setenv("DL4J_TPU_CHAOS", "tuner_misstep@1")
        chaos.reset_fault_points()
        # a real SLO engine with a real burning counter — no patching
        c = metrics_mod.counter("test_tuner_requests_total", "t",
                                ("outcome",))
        eng = slo_mod.configure([SloRule(
            name="tuner_acceptance", objective=0.99,
            bad=(Selector("test_tuner_requests_total",
                          exclude={"outcome": ("ok",)}),),
            total=(Selector("test_tuner_requests_total"),))])
        c.labels("ok").inc(10)
        eng.tick(now=1000.0)  # baseline sample (burn rates are deltas)

        t = tuner_mod.tuner()
        assert t is not None  # gate on -> armed
        # tick 1: the chaos point forces the deliberately bad decision
        out = t.tick(signals={"host_overhead_ms": 1.0, "step_ms": 100.0,
                              "verdict": "balanced"}, now=1.0)
        assert len(out) == 1 and out[0].reason == "chaos_misstep"
        assert envflags.int_value(WINDOW, 1) == rules_mod.WINDOW_MAX
        # the burn the misstep caused
        c.labels("error").inc(5)
        rows = eng.tick(now=1030.0)
        assert rows[0]["episodes"] == 1
        # tick 2 (the very next evaluation): the SLO gate reverts it
        out = t.tick(signals={}, now=2.0)
        assert len(out) == 1 and out[0].reason == "slo_revert"
        assert envflags.int_value(WINDOW, 1) == 1  # restored to default
        assert envflags.overrides() == {}
        st = t.status()
        assert st["decisions"] == 1 and st["reverts"] == 1
        # journal pins the whole arc: misstep then revert
        reasons = [e["reason"] for e in _journal()]
        assert reasons == ["chaos_misstep", "slo_revert"]
        # exactly ONE tuner_revert bundle, carrying the exact counts
        bundles = _bundles(tmp_path)
        assert len(bundles) == 1
        with open(tmp_path / "flight" / bundles[0]) as f:
            bundle = json.load(f)
        assert bundle["tuner"]["reverted"] == [WINDOW]
        assert bundle["tuner"]["decisions"] == 1
        assert bundle["tuner"]["reverts"] == 1


# ===========================================================================
# the engine closed loop + the gate-off zero-state contract
# ===========================================================================


class TestEngineClosedLoop:
    def test_epoch_ticks_widen_window_from_measured_signals(
            self, monkeypatch):
        """The loop measures, the epoch tick reads what it measured, the
        rule's decision re-keys the window the next epoch runs at. WHICH
        side of the threshold a CPU fit's own host share falls on depends
        on how loaded the machine is, so the tick is handed the loop's
        measured step time with the host's share of it pinned at 0.9."""
        from deeplearning4j_tpu.training import engine as engine_mod

        measured = []
        real = engine_mod.WindowedFitLoop.tuning_signals

        def host_bound(loop):
            sig = real(loop)
            measured.append(dict(sig))
            return dict(sig, host_overhead_ms=0.9 * sig["step_ms"])

        monkeypatch.setattr(engine_mod.WindowedFitLoop, "tuning_signals",
                            host_bound)
        monkeypatch.setenv("DL4J_TPU_AUTOTUNE", "1")
        net = _net()
        net.fit(ListDataSetIterator(_iris(), batch=10), epochs=2)
        t = tuner_mod.current()
        assert t is not None and t.ticks >= 2
        # epoch 1 at K=1: six batches, the first one's compile left out
        assert measured[0]["window"] == 1 and measured[0]["steps"] == 5
        assert 0 < measured[0]["host_overhead_ms"] <= measured[0]["step_ms"]
        entries = [e for e in _journal() if e["knob"] == WINDOW]
        assert entries and entries[0]["reason"] == "window_host_bound"
        assert entries[0]["signals"]["host_share"] == 0.9 >= \
            rules_mod.WINDOW_WIDEN_SHARE
        assert (entries[0]["old"], entries[0]["new"]) == (1, 2)
        assert envflags.effective(WINDOW)[1] == envflags.PROV_TUNER
        # the loop closed: epoch 2 ran at the window the tick chose
        assert measured[1]["window"] == 2

    def test_gate_off_allocates_zero_tuner_state(self, tmp_path):
        net = _net()
        net.fit(ListDataSetIterator(_iris(), batch=10), epochs=2)
        assert tuner_mod.current() is None  # no singleton
        assert envflags.overrides() == {}  # no overlay
        assert not os.path.exists(
            decisions_mod.journal_path())  # no journal
        st = tuner_mod.status()  # honest, and still not allocating
        assert st["enabled"] is False and st["ticks"] == 0
        assert tuner_mod.current() is None


class TestPrefetchLiveKnob:
    def test_depth_follows_override_when_not_pinned(self):
        a = AsyncDataSetIterator(ListDataSetIterator(_iris(), batch=10))
        try:
            assert a.prefetch_depth() == 4  # declared default
            envflags.set_override(PREFETCH, 8)
            assert a.prefetch_depth() == 8  # live: re-read, no rebuild
        finally:
            a.shutdown()

    def test_explicit_queue_size_stays_pinned(self):
        a = AsyncDataSetIterator(ListDataSetIterator(_iris(), batch=10),
                                 queue_size=2)
        try:
            envflags.set_override(PREFETCH, 8)
            assert a.prefetch_depth() == 2  # caller pinned -> knob inert
        finally:
            a.shutdown()


# ===========================================================================
# serving: reservoir -> re-cut -> warm swap -> warm revert
# ===========================================================================


class TestServingRecut:
    def _server(self, seen):
        def dispatch(x):
            seen.append(x.shape[0])
            return x * 2.0

        return InferenceServer(dispatch=dispatch, batch_limit=32,
                               queue_limit=64, wait_ms=0.0, name="recut")

    def test_recut_warms_new_sizes_before_swap(self):
        seen = []
        s = self._server(seen)
        try:
            s.warmup(np.zeros((1, 3), np.float32))
            for _ in range(64):  # rows of 5 pad to 8: waste 0.375
                s.output(np.zeros((5, 3), np.float32))
            assert len(s.observed_rows()) == 64
            t = tuner_mod.Tuner(now=lambda: 0.0)
            d = t.tick_serving(s, label="recut", now=1.0)
            assert d is not None and d.reason == "bucket_waste"
            assert list(s.buckets.sizes) == [5, 32]
            # the 5-bucket was dispatched once during the re-cut (warm)
            assert 5 in seen
            n_shapes = set(seen)
            s.output(np.zeros((5, 3), np.float32))
            assert set(seen) == n_shapes  # steady state: no new shape
        finally:
            s.shutdown()

    def test_slo_gate_reverts_recut_warm(self, monkeypatch, tmp_path):
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        trace_mod.configure(enabled=True)
        episodes = _patched_episodes(monkeypatch)
        seen = []
        s = self._server(seen)
        try:
            s.warmup(np.zeros((1, 3), np.float32))
            old_sizes = list(s.buckets.sizes)
            for _ in range(64):
                s.output(np.zeros((5, 3), np.float32))
            t = tuner_mod.Tuner(now=lambda: 0.0)
            t.tick_serving(s, label="recut", now=1.0)
            assert list(s.buckets.sizes) != old_sizes
            dispatches_before = len(seen)
            episodes[0] = 1
            out = t.tick(signals={}, now=2.0)
            assert [d.reason for d in out] == ["slo_revert"]
            assert list(s.buckets.sizes) == old_sizes  # cut restored
            # the revert re-installed already-warm sizes: zero dispatches
            assert len(seen) == dispatches_before
            assert len(_bundles(tmp_path)) == 1
        finally:
            s.shutdown()

    def test_request_rows_histogram_observes_demand(self):
        seen = []
        s = self._server(seen)
        try:
            s.warmup(np.zeros((1, 3), np.float32))
            s.output(np.zeros((5, 3), np.float32))
            snap = metrics_mod.registry().snapshot()
            hist = snap.get("dl4j_tpu_request_rows")
            assert hist is not None
        finally:
            s.shutdown()


# ===========================================================================
# the offline sweep
# ===========================================================================


@pytest.mark.slow
class TestSweep:
    def test_sweep_grid_and_restore(self):
        from deeplearning4j_tpu.tuning.sweep import run_sweep

        envflags.set_override(WINDOW, 2)  # a pre-existing overlay
        res = run_sweep(model="lenet", iters=2, batch=4,
                        windows=(1, 2), depths=(4,))
        assert len(res["grid"]) == 2
        assert res["best"] in res["grid"]
        assert res["default"]["window"] == 1
        assert res["speedup_vs_default"] is not None
        # the pre-sweep overlay is restored exactly
        assert envflags.overrides() == {WINDOW: "2"}
        # the winning cell is journaled as an advisory decision
        advisory = [e for e in _journal() if e["knob"] == "sweep"]
        assert advisory and advisory[-1]["applied"] is False


# ===========================================================================
# satellite 2: jaxlint JX021
# ===========================================================================


class TestJX021:
    def _rules(self, src, path="deeplearning4j_tpu/x/mod.py"):
        return [d.rule for d in jaxlint.lint_source(src, path)]

    def test_indirected_reads_fire(self):
        src = (
            "import os\n"
            "GATE = 'DL4J_TPU_FOO'\n"
            "a = os.getenv(GATE)\n"
            "b = os.environ.get(GATE)\n"
            "c = os.environ[GATE]\n"
        )
        assert self._rules(src).count("JX021") == 3

    def test_membership_and_read_modify_fire(self):
        src = (
            "import os\n"
            "GATE = 'DL4J_TPU_FOO'\n"
            "a = 'DL4J_TPU_FOO' in os.environ\n"
            "b = GATE in os.environ\n"
            "c = os.environ.pop('DL4J_TPU_FOO', None)\n"
            "d = os.environ.setdefault(GATE, '1')\n"
        )
        assert self._rules(src).count("JX021") == 4

    def test_attribute_assigned_gates_tracked(self):
        src = (
            "import os\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self.gate = 'DL4J_TPU_FOO'\n"
            "    def read(self):\n"
            "        return os.getenv(self.gate)\n"
        )
        assert "JX021" in self._rules(src)

    def test_literal_get_is_jx001_not_jx021(self):
        src = "import os\nv = os.environ.get('DL4J_TPU_FOO')\n"
        rules = self._rules(src)
        assert "JX001" in rules and "JX021" not in rules

    def test_non_gate_names_clean(self):
        src = (
            "import os\n"
            "OTHER = 'NOT_A_GATE'\n"
            "a = os.getenv(OTHER)\n"
            "b = os.getenv('HOME')\n"
            "c = 'PATH' in os.environ\n"
        )
        assert "JX021" not in self._rules(src)

    def test_envflags_is_exempt(self):
        src = "import os\nGATE = 'DL4J_TPU_FOO'\nv = os.getenv(GATE)\n"
        assert self._rules(
            src, "deeplearning4j_tpu/util/envflags.py") == []

    def test_pragma_suppresses(self):
        src = (
            "import os\n"
            "GATE = 'DL4J_TPU_FOO'\n"
            "v = os.getenv(GATE)  # jaxlint: disable=JX021\n"
        )
        assert "JX021" not in self._rules(src)

    def test_repo_is_clean(self):
        rep = jaxlint.lint_paths()
        assert [d for d in rep.diagnostics if d.rule == "JX021"] == []


# ===========================================================================
# satellite 3: knob snapshots in profile reports and flight bundles
# ===========================================================================


class TestKnobSnapshots:
    def test_flight_bundle_stamps_effective_knobs(
            self, monkeypatch, tmp_path):
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        trace_mod.configure(enabled=True)
        envflags.set_override(WINDOW, 4)
        path = flight_mod.dump("knob_stamp_test")
        assert path is not None
        with open(path) as f:
            bundle = json.load(f)
        assert bundle["knobs"][WINDOW] == {
            "value": "4", "provenance": envflags.PROV_TUNER}
        # the raw env section still records what the OPERATOR set —
        # the two sections answering different questions is the fix
        assert WINDOW not in bundle["env"]

    @pytest.mark.slow
    def test_profile_report_stamps_window_knobs(self):
        from deeplearning4j_tpu.telemetry import profiler

        envflags.set_override(WINDOW, 2)
        rep = profiler.profile_model(model="lenet", iters=2, batch=4)
        assert rep["knobs"][WINDOW]["provenance"] == envflags.PROV_TUNER
        text = profiler.format_report(rep)
        assert "knobs active during window" in text
        assert WINDOW in text


# ===========================================================================
# tune / config CLI
# ===========================================================================


class TestCli:
    def test_config_lists_provenance(self, monkeypatch, capsys):
        from deeplearning4j_tpu import cli

        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        envflags.set_override(WINDOW, 4)
        rc = cli.cmd_config(type("A", (), {"all": False, "json": True})())
        rows = {r["name"]: r for r in json.loads(capsys.readouterr().out)}
        assert rc == 0
        assert rows[WINDOW]["provenance"] == envflags.PROV_TUNER
        assert rows["DL4J_TPU_TELEMETRY"]["provenance"] == \
            envflags.PROV_ENV

    def test_config_exits_nonzero_on_undeclared(
            self, monkeypatch, capsys):
        from deeplearning4j_tpu import cli

        monkeypatch.setenv("DL4J_TPU_TYPO_GATE", "1")
        rc = cli.cmd_config(type("A", (), {"all": False, "json": True})())
        assert rc == 1

    def test_tune_log_renders_journal(self, monkeypatch, capsys):
        from deeplearning4j_tpu import cli

        _patched_episodes(monkeypatch)
        t = tuner_mod.Tuner(now=lambda: 0.0)
        t.tick(signals={"host_overhead_ms": 50.0, "step_ms": 100.0,
                        "verdict": "balanced"}, now=1.0)
        args = type("A", (), {"tune_cmd": "log", "limit": 10,
                              "clear": False, "json": True})()
        rc = cli.cmd_tune(args)
        entries = json.loads(capsys.readouterr().out)
        assert rc == 0 and entries[0]["knob"] == WINDOW

    def test_tune_status_honest_when_off(self, capsys):
        from deeplearning4j_tpu import cli

        args = type("A", (), {"tune_cmd": "status", "json": False})()
        rc = cli.cmd_tune(args)
        assert rc == 1
        assert "DL4J_TPU_AUTOTUNE" in capsys.readouterr().out


# ===========================================================================
# /tune endpoint
# ===========================================================================


class TestTuneEndpoint:
    def test_endpoint_serves_status_and_journal(self, monkeypatch):
        import urllib.request

        from deeplearning4j_tpu.ui import UIServer

        monkeypatch.setenv("DL4J_TPU_AUTOTUNE", "1")
        t = tuner_mod.tuner()
        t.tick(signals={"host_overhead_ms": 50.0, "step_ms": 100.0,
                        "verdict": "balanced"}, now=1.0)
        ui = UIServer(port=0)
        try:
            with urllib.request.urlopen(ui.url() + "/tune",
                                        timeout=5) as r:
                doc = json.loads(r.read())
            assert doc["tuner"]["enabled"] is True
            assert doc["tuner"]["decisions"] == 1
            assert doc["decisions"][0]["knob"] == WINDOW
        finally:
            ui.stop()
