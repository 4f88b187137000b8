"""Windowed device-resident training engine (training/engine.py).

The contract under test: rolling K optimizer steps into ONE jitted
lax.scan (`DL4J_TPU_STEP_WINDOW=K`) must be INDISTINGUISHABLE from K
per-step dispatches — params, updater state, and rng bitwise-equal
across MultiLayerNetwork, ComputationGraph, and ParallelWrapper; the
resilience contracts (resume equivalence, divergence sentry) must
survive windowing; and the async iterators' producer-side `place` hook
must keep their drain/shutdown lifecycle intact. Default (gate unset) is
one dispatch a step with ONE BATCH OF LOOK-AHEAD on the fit thread
(`TestLookAhead`): batch k+1 is handed to the runtime before step k's
score is read, and nothing else changes order — bitwise the loop that
feeds one batch at a time.
"""
import threading

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import (
    AsyncDataSetIterator,
    ExistingDataSetIterator,
    ListDataSetIterator,
)
from deeplearning4j_tpu.models import ComputationGraph, MultiLayerNetwork
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn import updaters
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import Dense, Output
from deeplearning4j_tpu.optimize.listeners import CollectScoresListener
from deeplearning4j_tpu.resilience import (
    ChaosDataSetIterator,
    CheckpointManager,
    DivergenceSentry,
)
from deeplearning4j_tpu.telemetry import trace as trace_mod
from deeplearning4j_tpu.training import engine

needs_8 = pytest.mark.skipif(len(jax.devices()) < 8,
                             reason="needs 8 virtual devices")

WINDOW_GATE = "DL4J_TPU" "_STEP_WINDOW"  # parse-time concat: a jaxlint JX001 fixture


def _mln(seed=7):
    conf = NeuralNetConfiguration(
        seed=seed, updater=updaters.Adam(learning_rate=5e-3),
    ).list([
        Dense(n_out=16, activation="relu"),
        Output(n_out=3, loss="mcxent"),
    ]).set_input_type(it.feed_forward(4))
    return MultiLayerNetwork(conf).init()


def _cg(seed=7):
    conf = (NeuralNetConfiguration(
                seed=seed, updater=updaters.Adam(learning_rate=5e-3)).graph()
            .add_inputs("in")
            .add_layer("h", Dense(n_out=16, activation="relu"), "in")
            .add_layer("out", Output(n_out=3, loss="mcxent"), "h")
            .set_outputs("out")
            .set_input_types(it.feed_forward(4))
            .build())
    return ComputationGraph(conf).init()


def _params(net):
    return {k: np.asarray(v) for k, v in net.get_param_table().items()}


def _opt_leaves(net):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(net.opt_state)]


def _assert_bitwise(a, b, what):
    assert len(a) == len(b)
    items = a.items() if isinstance(a, dict) else enumerate(a)
    bb = b if isinstance(b, dict) else list(b)
    for k, va in items:
        vb = bb[k]
        assert np.array_equal(np.asarray(va), np.asarray(vb),
                              equal_nan=True), f"{what}[{k}] differs"


# ===========================================================================
# gates
# ===========================================================================


class TestGates:
    def test_window_size_default_and_parse(self, monkeypatch):
        monkeypatch.delenv(WINDOW_GATE, raising=False)
        assert engine.window_size() == 1
        monkeypatch.setenv(WINDOW_GATE, "8")
        assert engine.window_size() == 8
        monkeypatch.setenv(WINDOW_GATE, "garbage")
        assert engine.window_size() == 1  # envflags garbage tolerance
        monkeypatch.setenv(WINDOW_GATE, "0")
        assert engine.window_size() == 1  # clamped, never 0

    def test_default_loop_is_not_windowed(self, monkeypatch):
        monkeypatch.delenv(WINDOW_GATE, raising=False)
        loop = engine.WindowedFitLoop(
            _mln(), raw_step=lambda *a: a, stage=lambda ds: None,
            dispatch=lambda args: None, exec_one=lambda ds: None)
        assert not loop.windowed and loop.window == 1
        with pytest.raises(ValueError, match="pair"):
            engine.WindowedFitLoop(_mln(), stage=lambda ds: None,
                                   exec_one=lambda ds: None)


# ===========================================================================
# K-step window == K single steps, bitwise (the tentpole contract)
# ===========================================================================


class TestWindowEquivalence:
    def _fit_pair(self, build, iris_like, monkeypatch, batch, epochs=2,
                  window="4"):
        it_ = ListDataSetIterator(iris_like, batch=batch)
        monkeypatch.delenv(WINDOW_GATE, raising=False)
        control = build()
        control.fit(it_, epochs=epochs)
        monkeypatch.setenv(WINDOW_GATE, window)
        windowed = build()
        windowed.fit(it_, epochs=epochs)
        return control, windowed

    def _assert_equal(self, control, windowed):
        assert windowed.iteration == control.iteration
        assert windowed.epoch == control.epoch
        _assert_bitwise(_params(control), _params(windowed), "params")
        _assert_bitwise(_opt_leaves(control), _opt_leaves(windowed),
                        "opt_state")
        assert np.array_equal(np.asarray(control._rng),
                              np.asarray(windowed._rng)), "rng diverged"
        assert windowed.score_ == pytest.approx(control.score_, abs=0.0)

    def test_mln_window_matches_per_step(self, iris_like, monkeypatch):
        """ACCEPTANCE: K=4 windows over 5 batches/epoch (one full window
        + a tail) leave params/updater-state/rng bitwise-equal to the
        per-step loop."""
        control, windowed = self._fit_pair(_mln, iris_like, monkeypatch,
                                           batch=30)
        self._assert_equal(control, windowed)

    def test_mln_window_8_and_ragged_tail_batch(self, iris_like,
                                                monkeypatch):
        """batch=40 over 150 samples: the 30-sample tail batch changes
        the step signature, forcing an early flush — shape churn must
        not break equivalence (nor recompile unboundedly)."""
        control, windowed = self._fit_pair(_mln, iris_like, monkeypatch,
                                           batch=40, window="8")
        self._assert_equal(control, windowed)

    def test_cg_window_matches_per_step(self, iris_like, monkeypatch):
        control, windowed = self._fit_pair(_cg, iris_like, monkeypatch,
                                           batch=30)
        self._assert_equal(control, windowed)

    def test_listeners_see_every_step(self, iris_like, monkeypatch):
        """The scan returns the per-step score vector and the engine
        replays it through iteration_done one step at a time: a score
        collector must record every iteration, in order."""
        monkeypatch.setenv(WINDOW_GATE, "4")
        net = _mln()
        col = CollectScoresListener()
        net.set_listeners(col)
        net.fit(ListDataSetIterator(iris_like, batch=30), epochs=2)
        assert [i for i, _ in col.scores] == list(range(1, 11))
        assert all(np.isfinite(s) for _, s in col.scores)

    @needs_8
    def test_parallel_wrapper_window_matches_per_step(self, rng,
                                                      monkeypatch):
        from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper

        n, f, c = 128, 8, 3
        x = rng.standard_normal((n, f)).astype(np.float32)
        ids = rng.integers(0, c, n)
        y = np.zeros((n, c), np.float32)
        y[np.arange(n), ids] = 1.0
        ds = DataSet(x, y)
        it_ = ListDataSetIterator(ds, batch=32)  # 4 batches = 1 window

        def build():
            conf = NeuralNetConfiguration(
                seed=11, updater=updaters.Adam(learning_rate=5e-3),
            ).list([
                Dense(n_out=16, activation="relu"),
                Output(n_out=c, loss="mcxent"),
            ]).set_input_type(it.feed_forward(f))
            return MultiLayerNetwork(conf).init()

        monkeypatch.delenv(WINDOW_GATE, raising=False)
        a = build()
        ParallelWrapper(a, mesh_spec=MeshSpec(data=8)).fit(it_, epochs=2)
        monkeypatch.setenv(WINDOW_GATE, "4")
        b = build()
        ParallelWrapper(b, mesh_spec=MeshSpec(data=8)).fit(it_, epochs=2)
        assert b.iteration == a.iteration
        _assert_bitwise(_params(a), _params(b), "params")
        _assert_bitwise(_opt_leaves(a), _opt_leaves(b), "opt_state")
        assert np.array_equal(np.asarray(a._rng), np.asarray(b._rng))


# ===========================================================================
# K=1: one batch of look-ahead == one batch at a time (the default loop)
# ===========================================================================


def _pw(seed=7):
    from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper

    return ParallelWrapper(_mln(seed), mesh_spec=MeshSpec(data=8))


def _net_of(model):
    return getattr(model, "model", model)  # the wrapper trains `.model`


def _look_ahead_batches(kind, rng):
    """`plain`: 3 equal batches; `masked`: the same with a per-example
    labels mask; `ragged`: a 30-row tail after three of 40 (a new step
    signature, and rows ParallelWrapper pads to its data axis)."""
    n = 150 if kind == "ragged" else 144
    x = rng.standard_normal((n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    lm = ((rng.random(n) < 0.7).astype(np.float32)
          if kind == "masked" else None)
    size = 40 if kind == "ragged" else 48
    return [DataSet(x[i:i + size], y[i:i + size], None,
                    None if lm is None else lm[i:i + size])
            for i in range(0, n, size)]


class _Batches:
    """An iterable over a list of DataSets that logs every `next()`."""

    def __init__(self, batches, events=None, fail_at=None):
        self.batches, self.events, self.fail_at = batches, events, fail_at

    def __iter__(self):
        for k, ds in enumerate(self.batches):
            if self.events is not None:
                self.events.append(("next", k))
            if k == self.fail_at:
                raise RuntimeError("iterator failed")
            yield ds


class _Recorder:
    """A listener that logs `iteration_done` and can raise at one."""

    def __init__(self, events, raise_at=None):
        self.events, self.raise_at = events, raise_at

    def iteration_done(self, model, iteration, score):
        self.events.append(("done", iteration - 1))
        if iteration == self.raise_at:
            raise RuntimeError("listener failed")

    def __getattr__(self, name):
        if name.startswith("on_"):
            return lambda *a, **k: None
        raise AttributeError(name)


def _recorded_loop(net, events, decline=()):
    """The model's own engine loop with its stage and dispatch logged
    (batch k is recognised by its first feature). Batches in `decline`
    are refused by `stage` and take `exec_one`, which here is the same
    step made one phase after the other."""
    if net._train_step is None:
        net._train_step = net._build_train_step()
    loop = net._engine_loop()
    stage0, dispatch0 = loop.stage, loop.dispatch
    index = {}

    def stage(ds):
        k = int(ds.features[0, 0])
        if k in decline:
            return None
        events.append(("put", k))
        staged = stage0(ds)
        index[id(staged[0])] = k
        return staged

    def dispatch(args):
        events.append(("dispatch", index[id(args)]))
        return dispatch0(args)

    def exec_one(ds):
        events.append(("exec_one", int(ds.features[0, 0])))
        args, b = stage0(ds)
        engine.finish_step(trace_mod.tracer(), net, dispatch0(args), b)

    loop.stage, loop.dispatch, loop.exec_one = stage, dispatch, exec_one
    return loop


def _numbered(n, rows=8):
    """n batches whose every feature is the batch's number."""
    y = np.eye(3, dtype=np.float32)[np.arange(rows) % 3]
    return [DataSet(np.full((rows, 4), k, np.float32), y) for k in range(n)]


class TestLookAhead:
    @pytest.mark.parametrize("kind", ["plain", "masked", "ragged"])
    @pytest.mark.parametrize("path", ["mln", "cg", "pw"])
    def test_matches_one_batch_at_a_time(self, path, kind, rng):
        """ACCEPTANCE: a fit over the iterator (batch k+1 handed to the
        runtime while step k runs) leaves parameters, updater state,
        every step's score and the rng bitwise where fits of ONE batch
        each, in the same order, leave them."""
        from deeplearning4j_tpu import telemetry

        if path == "pw" and len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        build = {"mln": _mln, "cg": _cg, "pw": _pw}[path]
        batches = _look_ahead_batches(kind, rng)

        ahead, col_a = build(), CollectScoresListener()
        _net_of(ahead).set_listeners(col_a)
        ahead.fit(ExistingDataSetIterator(batches), epochs=2)
        fit = telemetry.fit_log()[-1]
        assert fit["steps"] == 2 * len(batches)
        assert fit["staged_ahead"] == fit["steps"] - 2  # not an epoch's first

        serial, col_s = build(), CollectScoresListener()
        _net_of(serial).set_listeners(col_s)
        for _ in range(2):
            for ds in batches:
                serial.fit(ExistingDataSetIterator([ds]), epochs=1)
                assert telemetry.fit_log()[-1]["staged_ahead"] == 0

        a, b = _net_of(ahead), _net_of(serial)
        assert a.iteration == b.iteration == 2 * len(batches)
        assert col_a.scores == col_s.scores
        _assert_bitwise(_params(b), _params(a), "params")
        _assert_bitwise(_opt_leaves(b), _opt_leaves(a), "opt_state")
        assert np.array_equal(np.asarray(a._rng), np.asarray(b._rng))

    def test_order_put_ahead_listener_then_dispatch(self):
        """put(k+1) before iteration_done(k), iteration_done(k) before
        the dispatch of k+1, and never two staged batches outstanding."""
        events = []
        net = _mln()
        net.set_listeners(_Recorder(events))
        loop = _recorded_loop(net, events)
        loop.run_epoch(_Batches(_numbered(3), events))
        assert events == [
            ("next", 0), ("put", 0), ("dispatch", 0),
            ("next", 1), ("put", 1), ("done", 0), ("dispatch", 1),
            ("next", 2), ("put", 2), ("done", 1), ("dispatch", 2),
            ("done", 2)]
        outstanding = 0
        for what, _ in events:
            outstanding += {"put": 1, "dispatch": -1}.get(what, 0)
            assert 0 <= outstanding <= 1
        assert loop.staged_ahead == 2 and net.iteration == 3

    @pytest.mark.parametrize("fault", ["listener", "collective",
                                       "iterator", "exhausted"])
    def test_a_fault_leaves_the_staged_batch_undispatched(
            self, fault, monkeypatch):
        """An epoch that unwinds drops the batch staged ahead; a failing
        iterator lets the step in flight finish first (as a loop that
        reads it between steps would) and an exhausted one leaves
        nothing staged."""
        from deeplearning4j_tpu.resilience import ChaosError, chaos

        events = []
        net = _mln()
        net.set_listeners(_Recorder(
            events, raise_at=2 if fault == "listener" else None))
        loop = _recorded_loop(net, events)
        source = _Batches(_numbered(4), events,
                          fail_at=2 if fault == "iterator" else None)
        if fault == "collective":
            # ParallelWrapper's chaos site, as its fit wires it
            loop.on_dispatch = lambda: chaos.fault_point("collective")
            monkeypatch.setenv("DL4J_TPU_CHAOS", "collective@3")
            chaos.reset_fault_points()
        if fault == "exhausted":
            loop.run_epoch(source)
        else:
            with pytest.raises(ChaosError if fault == "collective"
                               else RuntimeError):
                loop.run_epoch(source)
        if fault == "collective":
            monkeypatch.delenv("DL4J_TPU_CHAOS")
            chaos.reset_fault_points()
        puts = [k for what, k in events if what == "put"]
        dispatched = [k for what, k in events if what == "dispatch"]
        done = [k for what, k in events if what == "done"]
        want = {"listener": ([0, 1, 2], [0, 1], [0, 1]),
                "collective": ([0, 1, 2], [0, 1], [0, 1]),
                "iterator": ([0, 1], [0, 1], [0, 1]),
                "exhausted": ([0, 1, 2, 3], [0, 1, 2, 3], [0, 1, 2, 3])}
        assert (puts, dispatched, done) == want[fault]
        assert net.iteration == len(done)

    def test_resumed_fit_ends_where_an_uninterrupted_one_does(
            self, tmp_path, iris_like):
        """A listener kills the fit mid-epoch 3 (a batch is staged ahead
        at that moment); a fresh model resumed through the manager ends
        bitwise where the uninterrupted fit does."""
        it_ = ListDataSetIterator(iris_like, batch=30)  # 5 batches/epoch
        control = _mln()
        control.fit(it_, epochs=4)
        cm = CheckpointManager(str(tmp_path))
        first = _mln()
        first.set_listeners(_Recorder([], raise_at=12))
        with pytest.raises(RuntimeError, match="listener failed"):
            first.fit(it_, epochs=4, checkpoint_manager=cm)
        assert first.epoch == 2
        resumed = _mln(seed=42)  # a fresh process would rebuild the net
        resumed.fit(it_, epochs=4, checkpoint_manager=cm)
        assert resumed.epoch == control.epoch == 4
        assert resumed.iteration == control.iteration == 20
        _assert_bitwise(_params(control), _params(resumed), "params")
        _assert_bitwise(_opt_leaves(control), _opt_leaves(resumed),
                        "opt_state")
        assert np.array_equal(np.asarray(control._rng),
                              np.asarray(resumed._rng))

    @pytest.mark.parametrize("kind", ["interleaved", "tbptt", "solver"])
    def test_unstageable_batches_keep_their_order(self, kind, rng):
        """Batches a path cannot stage go through exec_one in order, and
        none of them counts as staged ahead."""
        from deeplearning4j_tpu import telemetry

        if kind == "interleaved":
            events, ref_events = [], []
            net, ref = _mln(), _mln()
            loop = _recorded_loop(net, events, decline=(1, 4))
            loop.run_epoch(_Batches(_numbered(5), events))
            assert [e for e in events if e[0] != "next"] == [
                ("put", 0), ("dispatch", 0), ("exec_one", 1),
                ("put", 2), ("dispatch", 2), ("put", 3), ("dispatch", 3),
                ("exec_one", 4)]
            # batch 3 alone was handed over while a step ran
            assert loop.staged_ahead == 1
            _recorded_loop(ref, ref_events).run_epoch(_numbered(5))
            _assert_bitwise(_params(ref), _params(net), "params")
            assert np.array_equal(np.asarray(ref._rng),
                                  np.asarray(net._rng))
            return
        if kind == "tbptt":
            from deeplearning4j_tpu.nn.layers import LSTM, RnnOutput

            conf = NeuralNetConfiguration(
                seed=2, updater=updaters.Adam(learning_rate=0.02),
                backprop_type="tbptt", tbptt_fwd_length=5,
                tbptt_back_length=5,
            ).list([LSTM(n_out=4), RnnOutput(n_out=3, loss="mcxent")]
                   ).set_input_type(it.recurrent(4, 10))
            x = rng.standard_normal((12, 10, 4)).astype(np.float32)
            y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, (12, 10))]
            steps = 3 * 2   # 3 batches x 2 chunks of 5 of the 10 steps
        else:
            conf = NeuralNetConfiguration(
                seed=7, optimization_algo="lbfgs",
            ).list([Dense(n_out=8, activation="tanh"),
                    Output(n_out=3, loss="mcxent")]
                   ).set_input_type(it.feed_forward(4))
            x = rng.standard_normal((12, 4)).astype(np.float32)
            y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 12)]
            steps = 3
        net, col = MultiLayerNetwork(conf).init(), CollectScoresListener()
        net.set_listeners(col)
        net.fit(ListDataSetIterator(DataSet(x, y), batch=4), epochs=1)
        fit = telemetry.fit_log()[-1]
        assert fit["steps"] == steps and fit["staged_ahead"] == 0
        assert [i for i, _ in col.scores] == list(range(1, steps + 1))

    def test_fit_log_counts_the_look_ahead_and_phases_stay_leaves(
            self, iris_like, monkeypatch):
        """`staged_ahead == steps - 1`; `put` is entered once a step with
        the batch's bytes, as before the look-ahead; and no leaf phase
        opens inside another, so the fit's wall time less the leaf phases
        is still the loop's own time."""
        from deeplearning4j_tpu import telemetry

        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        tr = trace_mod.tracer()
        tr.clear()
        net = _mln()
        net.set_listeners(CollectScoresListener())
        net.fit(ListDataSetIterator(iris_like, batch=30), epochs=1)
        fit = telemetry.fit_log()[-1]
        assert fit["steps"] == 5 and fit["staged_ahead"] == 4
        assert fit["phases"]["put"]["calls"] == 5
        assert fit["phases"]["put"]["bytes"] == (iris_like.features.nbytes
                                                 + iris_like.labels.nbytes)
        leaves = ("etl", "put", "dispatch", "score_wait", "listeners")
        for name in leaves:
            assert fit["phases"][name]["calls"] == 5, name
        me = threading.get_ident()
        spans = sorted((r.start, r.start + r.duration_ms / 1e3, r.name)
                       for r in tr.records()
                       if r.name in leaves and r.thread_id == me)
        assert len(spans) == 25
        for (_, end, a), (start, _, b) in zip(spans, spans[1:]):
            assert end <= start + 1e-9, f"{b} opened inside {a}"

    def test_an_interrupt_unwinds_past_the_listeners(self):
        """Ctrl-C while the loop waits for batch k+1 goes straight up:
        step k was dispatched, but its score is not waited for and no
        listener (a checkpoint writer among them) runs on the way."""
        events = []

        def interrupted():
            yield from _numbered(2)
            raise KeyboardInterrupt

        net = _mln()
        net.set_listeners(_Recorder(events))
        loop = _recorded_loop(net, events)
        with pytest.raises(KeyboardInterrupt):
            loop.run_epoch(interrupted())
        assert [e for e in events if e[0] != "put"] == [
            ("dispatch", 0), ("done", 0), ("dispatch", 1)]

    def test_a_starved_fit_still_reads_input_bound(self, monkeypatch):
        """The `step` record, the step histogram's yardstick, is the
        step's own time: the `etl` and `put` of batch k+1 open inside
        step k's span but are not counted into it, so a real K=1 fit
        over a slow iterator reads `input_bound` (the verdict the
        tuner's prefetch rule and docs/PERFORMANCE.md step 1 act on)."""
        import time

        from deeplearning4j_tpu.telemetry import health as health_mod

        class Slow(ExistingDataSetIterator):
            def __next__(self):
                time.sleep(0.03)
                return ExistingDataSetIterator.__next__(self)

            def async_supported(self):
                return False

        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        net = _mln()
        net.fit(ExistingDataSetIterator(_numbered(2)), epochs=1)  # compile
        tr = trace_mod.tracer()
        tr.clear()
        net.fit(Slow(_numbered(6)), epochs=1)
        v = health_mod.input_verdict()
        assert v["verdict"] == "input_bound", v
        assert v["etl_p50_ms"] >= 30 > v["step_p50_ms"]
        steps = [r for r in tr.records() if r.name == "step"]
        assert len(steps) == 6

    @pytest.mark.parametrize("path", ["mln", "pw"])
    def test_a_source_may_recycle_a_buffer_two_batches_later(self, path,
                                                             rng):
        """The iterator contract the look-ahead leaves: a yielded batch
        stays untouched through the NEXT `next()` (its transfer may
        still run then) and may be overwritten from the one after —
        step k's score has been read by then. A source that rotates two
        buffers trains bitwise as one that yields fresh arrays."""
        if path == "pw" and len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        build = {"mln": _mln, "pw": _pw}[path]
        batches = _look_ahead_batches("plain", rng) * 2

        class Recycling(ExistingDataSetIterator):
            """Copies each batch into one of two buffers it owns; not
            for a prefetch queue, which would hold more than two."""
            bufs = [(np.empty_like(batches[0].features),
                     np.empty_like(batches[0].labels)) for _ in range(2)]

            def __next__(self):
                x, y = self.bufs[self._pos % 2]
                ds = ExistingDataSetIterator.__next__(self)
                x[...] = ds.features
                y[...] = ds.labels
                return DataSet(x, y)

            def async_supported(self):
                return False

        fresh, recycled = build(), build()
        fresh.fit(ExistingDataSetIterator(batches), epochs=1)
        recycled.fit(Recycling(batches), epochs=1)
        _assert_bitwise(_params(_net_of(fresh)), _params(_net_of(recycled)),
                        "params")


# ===========================================================================
# resilience contracts survive windowing
# ===========================================================================


class TestWindowedResilience:
    def test_resume_equivalence_windowed(self, tmp_path, iris_like,
                                         monkeypatch):
        """fit2 + resume + fit2 == fit4 with DL4J_TPU_STEP_WINDOW=4 —
        the preemption contract is window-size-independent."""
        monkeypatch.setenv(WINDOW_GATE, "4")
        it_ = ListDataSetIterator(iris_like, batch=30)
        control = _mln()
        control.fit(it_, epochs=4,
                    checkpoint_manager=CheckpointManager(
                        str(tmp_path / "control")))
        cm = CheckpointManager(str(tmp_path / "resumable"))
        first = _mln()
        first.fit(it_, epochs=2, checkpoint_manager=cm)
        resumed = _mln()
        resumed.fit(it_, epochs=4, checkpoint_manager=cm)
        assert resumed.epoch == control.epoch == 4
        assert resumed.iteration == control.iteration
        _assert_bitwise(_params(control), _params(resumed), "params")
        assert np.array_equal(np.asarray(control._rng),
                              np.asarray(resumed._rng))

    def test_sentry_trips_on_nan_mid_window(self, iris_like, monkeypatch):
        """A NaN batch at window position 2 of 4: the whole window ran
        on device before any host look, but the per-step score replay
        still trips the sentry, which restores the clean PRE-WINDOW
        snapshot (on_window_start) and the run finishes finite.
        CRITICAL: ONE divergence event consumes ONE rollback — the
        burst's remaining NaN scores describe discarded steps and must
        NOT burn the budget (max_rollbacks=2 survives)."""
        monkeypatch.setenv(WINDOW_GATE, "4")
        net = _mln()
        sentry = DivergenceSentry(policy="skip_batch", max_rollbacks=2,
                                  snapshot_every=1)
        net.set_listeners(sentry)
        chaotic = ChaosDataSetIterator(
            ListDataSetIterator(iris_like, batch=30), nan_at=(2,))
        net.fit(chaotic, epochs=1)
        assert sentry.divergences == 1
        assert sentry.rollbacks == 1
        assert np.isfinite(net.score_)
        for k, v in _params(net).items():
            assert np.isfinite(v).all(), k

    def test_sentry_windowed_state_resets_between_fits(self, iris_like,
                                                       monkeypatch):
        """A windowed fit must not permanently coarsen the sentry: a
        LATER per-step fit on the same sentry still detects and
        restores per-iteration snapshots."""
        net = _mln()
        sentry = DivergenceSentry(policy="skip_batch", max_rollbacks=2,
                                  snapshot_every=1)
        net.set_listeners(sentry)
        monkeypatch.setenv(WINDOW_GATE, "4")
        net.fit(ListDataSetIterator(iris_like, batch=30), epochs=1)
        monkeypatch.delenv(WINDOW_GATE, raising=False)
        chaotic = ChaosDataSetIterator(
            ListDataSetIterator(iris_like, batch=30), nan_at=(3,))
        net.fit(chaotic, epochs=1)
        assert not sentry._windowed
        assert sentry.rollbacks == 1
        for k, v in _params(net).items():
            assert np.isfinite(v).all(), k

    def test_checkpoint_listener_defers_mid_window_saves(self, tmp_path,
                                                         iris_like,
                                                         monkeypatch):
        """An iteration-cadence checkpoint trigger that fires mid-burst
        (params already window-end, iteration mid-window) must defer to
        the window boundary: every saved manifest's step is a boundary,
        so restore_into + continue never double-applies steps."""
        from deeplearning4j_tpu.resilience import CheckpointListener

        monkeypatch.setenv(WINDOW_GATE, "4")
        net = _mln()
        cm = CheckpointManager(str(tmp_path))
        net.set_listeners(CheckpointListener(cm, save_every_n_iterations=2))
        # 5 batches/epoch -> windows of 4 + 1; triggers at iters 2 and 4
        # both land inside the first burst and flush ONCE at boundary 4
        net.fit(ListDataSetIterator(iris_like, batch=30), epochs=1)
        steps = [m["step"] for m in cm.manifests()]
        assert steps == [4]
        # the boundary save is consistent: restoring it yields exactly
        # the state a PER-STEP run checkpoints at iteration 4
        monkeypatch.delenv(WINDOW_GATE, raising=False)
        control = _mln()
        cm2 = CheckpointManager(str(tmp_path / "ctl"))
        control.set_listeners(
            CheckpointListener(cm2, save_every_n_iterations=4))
        control.fit(ListDataSetIterator(iris_like, batch=30), epochs=1)
        ctl, restored = _mln(), _mln()
        cm2.restore_into(ctl)
        cm.restore_into(restored)
        assert restored.iteration == ctl.iteration == 4
        _assert_bitwise(_params(ctl), _params(restored), "params")

    def test_rollback_stops_replay_no_ghost_iterations(self, iris_like,
                                                       monkeypatch):
        """After a mid-burst restore, the engine must STOP the replay:
        the counter stays at the restored boundary plus genuinely
        applied windows, and other listeners never see the discarded
        steps' iterations/scores."""
        monkeypatch.setenv(WINDOW_GATE, "4")
        net = _mln()
        col = CollectScoresListener()
        sentry = DivergenceSentry(policy="skip_batch", max_rollbacks=2,
                                  snapshot_every=1)
        net.set_listeners(col, sentry)
        chaotic = ChaosDataSetIterator(
            ListDataSetIterator(iris_like, batch=30), nan_at=(2,))
        net.fit(chaotic, epochs=1)
        # window 1 (batches 1-4) replays iters 1, 2(NaN->trip, restore
        # to 0, break; batches 3-4 discarded); tail window = batch 5 ->
        # iteration 1. No ghost iterations 3/4 anywhere.
        assert sentry.rollbacks == 1
        assert net.iteration == 1
        assert [i for i, _ in col.scores] == [1, 2, 1]
        assert np.isfinite(net.score_)

    def test_sentry_warn_policy_detects_mid_window(self, iris_like,
                                                   monkeypatch):
        monkeypatch.setenv(WINDOW_GATE, "4")
        net = _mln()
        sentry = DivergenceSentry(policy="warn")
        net.set_listeners(sentry)
        chaotic = ChaosDataSetIterator(
            ListDataSetIterator(iris_like, batch=30), nan_at=(3,))
        net.fit(chaotic, epochs=1)
        assert sentry.divergences >= 1 and sentry.rollbacks == 0


# ===========================================================================
# the async iterators' producer-side `place` hook
# ===========================================================================


class TestDevicePrefetch:
    def _base(self, n=6):
        """One DataSet sliced into n 4-row batches; batch i's features
        are the constant i, so payload integrity is checkable."""
        x = np.repeat(np.arange(n, dtype=np.float32), 4)[:, None]
        x = np.tile(x, (1, 4))
        return ListDataSetIterator(
            DataSet(x, np.ones((4 * n, 3), np.float32)), batch=4)

    def test_place_runs_on_producer_thread(self):
        seen = []
        main = threading.get_ident()

        def place(ds):
            seen.append(threading.get_ident())
            return engine.place_batch(ds, jax.device_put)

        ait = AsyncDataSetIterator(self._base(), place=place)
        got = list(ait)
        ait.shutdown()
        assert len(got) == len(seen) == 6
        assert all(t != main for t in seen), "place ran on the consumer"
        assert all(isinstance(d.features, jax.Array) for d in got)
        # payload untouched by placement
        assert [float(d.features[0, 0]) for d in got] == [0, 1, 2, 3, 4, 5]

    def test_reset_mid_stream_drains_cleanly(self):
        ait = AsyncDataSetIterator(
            self._base(), queue_size=2,
            place=lambda d: engine.place_batch(d, jax.device_put))
        it1 = iter(ait)
        next(it1), next(it1)  # producer mid-stream, queue part-full
        ait.reset()
        assert len(list(ait)) == 6  # full pass after reset
        ait.shutdown()
        t = ait._thread
        assert t is None or not t.is_alive()

    def test_shutdown_idempotent_with_place(self):
        ait = AsyncDataSetIterator(
            self._base(),
            place=lambda d: engine.place_batch(d, jax.device_put))
        next(iter(ait))
        ait.shutdown()
        ait.shutdown()  # second call must be a no-op

    def test_producer_place_error_surfaces_on_consumer(self):
        def bad(ds):
            raise RuntimeError("transfer failed")

        ait = AsyncDataSetIterator(self._base(), place=bad)
        with pytest.raises(RuntimeError, match="transfer failed"):
            list(ait)
        ait.shutdown()


# ===========================================================================
# engine internals
# ===========================================================================


class TestEngineInternals:
    def test_build_window_scan_matches_manual_steps(self):
        """The scanned program == K manual raw-step applications with the
        host key schedule (split-then-use), bitwise."""
        import jax.numpy as jnp

        def raw(params, state, opt, itn, rng, x, y, fm, lm):
            noise = jax.random.normal(rng, params.shape)
            p = params - 0.1 * (params - x.mean()) + 0.0 * noise
            return p, state, opt, (p * y).sum()

        k = 4
        scan = engine.build_window_scan(raw, k, watch_name="t")
        p0 = jnp.arange(4.0)
        xs = jnp.stack([jnp.full((3,), i, jnp.float32) for i in range(k)])
        ys = jnp.stack([jnp.ones((4,))] * k)
        window = (xs, ys, None, None)
        rng0 = jax.random.PRNGKey(0)
        # manual replay first (the scan donates its carry), through a
        # per-step jit — the contract is jitted-step == scanned-step,
        # not eager == compiled (eager op-by-op rounding differs)
        jraw = jax.jit(raw)
        pm, rm = p0, rng0
        out = []
        for i in range(k):
            rm, sub = jax.random.split(rm)
            pm, _, _, sc = jraw(pm, (), (), jnp.asarray(5 + i), sub,
                                xs[i], ys[i], None, None)
            out.append(float(sc))
        p, s, o, rng, scores = scan(jnp.arange(4.0), (), (),
                                    jax.random.PRNGKey(0), jnp.asarray(5),
                                    window)
        assert np.array_equal(np.asarray(p), np.asarray(pm))
        assert np.array_equal(np.asarray(rng), np.asarray(rm))
        np.testing.assert_allclose(np.asarray(scores), out, rtol=1e-6)

    def test_signature_distinguishes_mask_structure(self):
        import jax.numpy as jnp

        a = engine._signature((jnp.ones((2, 3)), None))
        b = engine._signature((jnp.ones((2, 3)), jnp.ones((2,))))
        c = engine._signature((jnp.ones((2, 4)), None))
        assert a != b and a != c

    def test_exception_mid_epoch_drops_staged_batches(self, monkeypatch,
                                                      iris_like):
        """A chaos fault between stage and dispatch must not dispatch
        the staged-but-unapplied tail during unwind (resume replays the
        epoch from its checkpoint instead)."""
        monkeypatch.setenv(WINDOW_GATE, "4")
        net = _mln()
        chaotic = ChaosDataSetIterator(
            ListDataSetIterator(iris_like, batch=30), fail_at=(3,))
        from deeplearning4j_tpu.resilience import ChaosError
        with pytest.raises(ChaosError):
            net.fit(chaotic, epochs=1)
        # batches 1-2 were staged but the window never filled: nothing
        # may have been applied
        assert net.iteration == 0
