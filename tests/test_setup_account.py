"""The set-up account (ISSUE 49): the always-on compile account behind
`fit_log()`'s `compile`, the `init` / `place` / `import` spans behind
`setup_log()`, the watcher's counts read off the one account, a warm
window that calls no listener, and the benchmark's six `setup_*` readers
on a `fit_log()` and a `setup_log()` put there by hand."""
from types import SimpleNamespace as NS

import numpy as np
import pytest

from benchmark import harness
from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.models import ComputationGraph, MultiLayerNetwork
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn import updaters
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import Dense, Output
from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper
from deeplearning4j_tpu.telemetry import health as health_mod
from deeplearning4j_tpu.telemetry import introspect
from deeplearning4j_tpu.telemetry import trace as trace_mod

STAGES = ("traces", "trace_s", "lower_s", "backend_compiles",
          "backend_compile_s")
CACHE = ("cache_hits", "cache_misses", "cache_retrieval_s",
         "compile_time_saved_s")


def _conf(width):
    return NeuralNetConfiguration(
        seed=1, updater=updaters.Adam(learning_rate=5e-3),
    ).list([
        Dense(n_out=width, activation="relu"),
        Output(n_out=3, loss="mcxent"),
    ]).set_input_type(it.feed_forward(4))


def _wrapper(width=16):
    return ParallelWrapper(MultiLayerNetwork(_conf(width)).init(),
                           mesh_spec=MeshSpec(data=8))


def _rows(n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    return DataSet(x, np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)])


def _fit(pw, rows, batch):
    pw.fit(ListDataSetIterator(rows, batch=batch), epochs=1)
    return telemetry.fit_log()[-1]


@pytest.fixture(autouse=True)
def _gate_off(monkeypatch):
    monkeypatch.delenv("DL4J_TPU_TELEMETRY", raising=False)
    trace_mod.configure(enabled=None)
    assert not trace_mod.tracer().enabled
    yield
    # the one gate-on test leaves a ring, fingerprints and a heartbeat
    trace_mod.configure(enabled=None)
    trace_mod.tracer().clear()
    introspect.reset()
    health_mod.reset_for_tests()


# ===========================================================================
# the account on events put there by hand
# ===========================================================================


def _feed(acct, *events):
    for event, seconds, name in events:
        acct.on_duration(event, seconds, **({} if name is None
                                            else {"fun_name": name}))


class TestCompileAccount:
    def test_one_key_a_function_and_each_traced_second_once(self):
        """JAX's own order for `jit(step)`: the inner functions' traces,
        the outer's (which holds them), the lowering and the backend under
        `jit(step)`."""
        acct = introspect.CompileAccount()
        mark = acct.mark()
        _feed(acct,
              (introspect.TRACE_EVENT, 0.001, "matmul"),
              (introspect.TRACE_EVENT, 0.002, "tanh"),
              (introspect.TRACE_EVENT, 0.010, "step"),
              (introspect.LOWER_EVENT, 0.020, "jit(step)"),
              (introspect.BACKEND_EVENT, 0.300, "jit(step)"))
        got = acct.since(mark)
        assert got["by_fn"] == {"step": {
            "traces": 1, "trace_s": 0.010, "lower_s": 0.020,
            "backend_compiles": 1, "backend_compile_s": 0.300}}
        assert {k: got[k] for k in STAGES} == got["by_fn"]["step"]
        assert {k: got[k] for k in CACHE} == dict.fromkeys(CACHE, 0)

    def test_what_a_kernel_s_lowering_traces_does_not_take_the_step_s_trace(self):
        """On a TPU the lowering of a Pallas call traces jitted helpers
        BETWEEN the step's trace event and its lowering event (my chip
        run, PR 49: a first version kept only the newest trace and booked
        0 s of tracing to every real step)."""
        acct = introspect.CompileAccount()
        _feed(acct,
              (introspect.TRACE_EVENT, 0.2, "step"),      # the model's, inside
              (introspect.TRACE_EVENT, 10.0, "step"),     # the wrapper's, around it
              (introspect.TRACE_EVENT, 0.001, "_where"),  # while a kernel is lowered
              (introspect.LOWER_EVENT, 3.0, "jit(step)"),
              (introspect.TRACE_EVENT, 0.002, "convert_element_type"),
              (introspect.LOWER_EVENT, 0.004, "jit(convert_element_type)"))
        got = acct.since(introspect._tally())
        assert got["by_fn"]["step"]["trace_s"] == 10.0
        assert got["by_fn"]["step"]["traces"] == 1
        assert got["trace_s"] == pytest.approx(10.002) and got["traces"] == 2

    def test_a_trace_nobody_lowered_is_not_the_next_lowering_s(self):
        acct = introspect.CompileAccount()
        _feed(acct,
              (introspect.TRACE_EVENT, 5.0, "shape_only"),   # an eval_shape
              (introspect.LOWER_EVENT, 0.02, "jit(other)"),
              (introspect.LOWER_EVENT, 0.03, "jit(other)"))
        got = acct.since(introspect._tally())
        assert got["traces"] == 0 and got["trace_s"] == 0.0
        assert got["by_fn"]["other"]["lower_s"] == pytest.approx(0.05)

    def test_a_nameless_lowering_takes_the_trace_before_it(self):
        acct = introspect.CompileAccount()
        _feed(acct, (introspect.TRACE_EVENT, 0.5, "step"),
              (introspect.LOWER_EVENT, 0.1, "jit(<unknown>)"))
        assert acct.since(introspect._tally())["by_fn"]["<unknown>"][
            "trace_s"] == 0.5

    def test_the_cache_s_numbers_have_no_name(self):
        acct = introspect.CompileAccount()
        mark = acct.mark()
        acct.on_event(introspect.CACHE_MISS_EVENT)
        acct.on_event(introspect.CACHE_HIT_EVENT)
        acct.on_event(introspect.CACHE_HIT_EVENT)
        acct.on_event("/jax/compilation_cache/compile_requests_use_cache")
        _feed(acct, (introspect.CACHE_READ_EVENT, 0.25, None),
              (introspect.CACHE_SAVED_EVENT, -0.05, None),
              ("/jax/some/other_duration", 9.0, None))
        got = acct.since(mark)
        assert {k: got[k] for k in CACHE} == {
            "cache_hits": 2, "cache_misses": 1, "cache_retrieval_s": 0.25,
            "compile_time_saved_s": -0.05}
        assert got["by_fn"] == {} and got["backend_compiles"] == 0

    def test_claims_add_up_and_outside_is_the_rest(self):
        acct = introspect.CompileAccount()
        _feed(acct, (introspect.BACKEND_EVENT, 1.0, "jit(_normal)"))
        mark = acct.mark()
        _feed(acct, (introspect.BACKEND_EVENT, 0.1, "jit(step)"))
        first = acct.claim("fit", mark)
        mark = acct.mark()
        _feed(acct, (introspect.BACKEND_EVENT, 0.2, "jit(step)"))
        acct.claim("fit", mark)
        assert first["by_fn"]["step"]["backend_compile_s"] == 0.1
        fits = acct.claimed("fit")
        assert fits["backend_compiles"] == 2
        assert fits["backend_compile_s"] == pytest.approx(0.3)
        out = acct.outside("fit")
        assert list(out["by_fn"]) == ["_normal"]
        assert out["backend_compile_s"] == 1.0
        assert acct.claimed("init")["backend_compiles"] == 0
        assert acct.total("backend_compiles") == 3


# ===========================================================================
# what a fit says it compiled
# ===========================================================================


class TestFitCompileEntry:
    def test_first_fit_names_its_step_warm_fit_reads_zeros_new_shape_names_it_again(self):
        pw = _wrapper()
        rows = _rows(48)
        first = _fit(pw, rows, 24)
        c = first["compile"]
        assert c["traces"] >= 1 and c["trace_s"] > 0 and c["lower_s"] > 0
        assert c["backend_compiles"] >= 1 and c["backend_compile_s"] > 0
        assert c["backend_compiles"] == first["compiles"]
        step = c["by_fn"]["step"]
        assert step["traces"] == 1 and step["backend_compiles"] == 1
        # the library's jitted functions traced INSIDE the step are not
        # listed, and their seconds are counted once: in the step's
        assert not {"dot_general", "matmul", "_reduce_sum", "tanh"} & set(c["by_fn"])
        for k in STAGES:
            assert c[k] == pytest.approx(sum(f[k] for f in c["by_fn"].values()))
        assert c["trace_s"] + c["lower_s"] + c["backend_compile_s"] <= first["wall_s"]

        warm = _fit(pw, rows, 24)
        assert warm["compiles"] == 0
        assert warm["compile"]["by_fn"] == {}
        assert all(warm["compile"][k] == 0 for k in STAGES + CACHE)

        again = _fit(pw, rows, 16)          # which step recompiled
        assert list(again["compile"]["by_fn"]) == ["step"]
        assert again["compile"]["traces"] == 1
        assert again["compiles"] == 1

    def test_fits_lie_in_order_on_one_clock(self):
        pw = _wrapper()
        rows = _rows(48)
        a, b = _fit(pw, rows, 24), _fit(pw, rows, 24)
        assert 0 < a["t_start_s"] < a["t_start_s"] + a["wall_s"] <= b["t_start_s"]
    
    @pytest.mark.parametrize("path", ["MultiLayerNetwork.fit", "ComputationGraph.fit"])
    def test_the_models_own_fits_carry_the_entry_too(self, path):
        if path == "MultiLayerNetwork.fit":
            net = MultiLayerNetwork(_conf(16)).init()
        else:
            net = ComputationGraph(
                NeuralNetConfiguration(seed=1, updater=updaters.Adam(learning_rate=5e-3))
                .graph().add_inputs("in")
                .add_layer("d", Dense(n_out=8, activation="relu"), "in")
                .add_layer("out", Output(n_out=3, loss="mcxent"), "d")
                .set_outputs("out").set_input_types(it.feed_forward(4))).init()
        fit = _fit(net, _rows(40), 20)
        assert fit["path"] == path
        assert fit["compile"]["backend_compiles"] == fit["compiles"] >= 1
        assert any(f["traces"] for f in fit["compile"]["by_fn"].values())

    def test_a_refit_after_a_restart_is_a_cache_read(self):
        """The cache placed as tests/test_serving_fleet.py places it (the
        process's one directory, every compile kept). A FRESH network of
        the same configuration is what a restarted process builds: a new
        jit wrapper, a new trace, the same HLO — the backend's work is a
        read of the cache. (`jax.clear_caches()` fires the same events;
        it would also make every later test of this worker recompile.)"""
        import jax

        from deeplearning4j_tpu.util import compile_cache

        compile_cache.ensure()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        try:
            rows = _rows(56, seed=3)
            _fit(_wrapper(width=13), rows, 56)            # written, or there
            c = _fit(_wrapper(width=13), rows, 56)["compile"]
        finally:
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        assert c["by_fn"]["step"]["traces"] == 1
        assert c["cache_hits"] >= 1 and c["cache_retrieval_s"] > 0
        assert c["backend_compiles"] - c["cache_hits"] == 0
        assert c["cache_misses"] == 0


# ===========================================================================
# the three spans, setup_log(), and the one tally
# ===========================================================================


class TestSetupLog:
    def test_init_and_place_are_spans_and_init_holds_its_compiles(self):
        before = telemetry.setup_log()
        # a width no other test initialises: the initialisers compile
        pw = _wrapper(width=41)
        after = telemetry.setup_log()
        assert after["init"]["calls"] == before["init"]["calls"] + 1
        assert after["init"]["total_s"] > before["init"]["total_s"]
        new = (after["init"]["compile"]["backend_compiles"]
               - before["init"]["compile"]["backend_compiles"])
        assert new >= 1
        assert after["init"]["compile"]["by_fn"]
        # no fit ran: what init compiled is also "outside fits"
        assert (after["compile_outside_fits"]["backend_compiles"]
                - before["compile_outside_fits"]["backend_compiles"]) == new
        assert after["import_s"] == before["import_s"] > 0
        assert trace_mod.tracer().account.snapshot()["import"]["calls"] == 1

        # the wrapper places the model on its mesh at its first fit,
        # before the fit's clock starts: `place` is in no fit's phases
        fit = _fit(pw, _rows(40), 40)
        placed = telemetry.setup_log()["place"]
        assert placed["calls"] == before["place"]["calls"] + 1
        assert placed["total_s"] > before["place"]["total_s"]
        assert "place" not in fit["phases"] and "init" not in fit["phases"]

    def test_what_fits_compile_is_not_outside_them(self):
        pw = _wrapper(width=19)
        before = telemetry.setup_log()["compile_outside_fits"]
        fit = _fit(pw, _rows(40), 40)
        assert fit["compiles"] >= 1
        after = telemetry.setup_log()["compile_outside_fits"]
        assert after["backend_compiles"] == before["backend_compiles"]
        assert after["backend_compile_s"] == pytest.approx(before["backend_compile_s"])

    def test_the_spans_reach_the_ring_with_the_gate_on(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        tr = trace_mod.tracer()
        tr.clear()
        _fit(_wrapper(), _rows(40), 40)
        names = [r.name for r in tr.records() if r.category == "setup"]
        assert names == ["init", "place"]

    def test_the_watcher_reads_the_one_account(self):
        w = introspect.watcher()
        _fit(_wrapper(), _rows(48), 48)
        total = w.account.total
        assert w.compile_count() == total("backend_compiles") > 0
        assert w.cache_hit_count() == total("cache_hits")
        assert w.cold_compile_count() == (total("backend_compiles")
                                          - total("cache_hits"))
        snap = w.snapshot()
        assert snap["backend_compiles"] == w.compile_count()
        assert snap["persistent_cache_hits"] == w.cache_hit_count()
        assert snap["backend_compile_seconds"] == round(total("backend_compile_s"), 4)
        assert snap["cache_retrieval_seconds"] == round(total("cache_retrieval_s"), 4)
        assert snap["cache_retrieval_seconds"] <= snap["backend_compile_seconds"]


def test_no_listener_runs_in_a_warm_window(monkeypatch):
    pw = _wrapper()
    rows = _rows(160)
    _fit(pw, rows, 8)                                  # compiled, warm
    acct = introspect.watcher().account
    calls = []
    for name in ("on_duration", "on_event"):
        real = getattr(acct, name)
        monkeypatch.setattr(acct, name, lambda *a, _real=real, **k: (
            calls.append(a[0]), _real(*a, **k))[1])
    fit = _fit(pw, rows, 8)
    assert fit["steps"] == 20 and calls == []
    _fit(pw, rows, 32)                                 # the control: a new shape
    assert introspect.BACKEND_EVENT in calls


# ===========================================================================
# the benchmark's six readers
# ===========================================================================

READERS = ("setup_import_s.train", "setup_init_s.train",
           "setup_trace_lower_s.train", "setup_compile_s.train",
           "setup_cold_compiles.train", "setup_program_s.train")


def compiled(trace_s=0.0, lower_s=0.0, backend=0, backend_s=0.0, hits=0):
    return {"traces": 1 if trace_s else 0, "trace_s": trace_s, "lower_s": lower_s,
            "backend_compiles": backend, "backend_compile_s": backend_s,
            "cache_hits": hits, "cache_misses": backend - hits,
            "cache_retrieval_s": 0.0, "by_fn": {}}


def logged(steps, wall_s, **kw):
    return {"path": "ParallelWrapper.fit", "steps": steps, "wall_s": wall_s,
            "compiles": kw.get("backend", 0), "compile": compiled(**kw),
            "phases": {}}


def run_view(steps=10, window_s=2.0):
    return NS(counters={"steps": steps, "window_s": window_s},
              cell={"name": "setup_account_test_cell", "chips": 1})


SETUP = {"import_s": 1.5,
         "init": {"calls": 1, "total_s": 3.0,
                  "compile": compiled(backend=7, backend_s=2.0, hits=5)},
         "place": {"calls": 1, "total_s": 0.25},
         "compile_outside_fits": compiled(backend=9, backend_s=2.5, hits=5)}

FITS = [logged(1, 20.0, trace_s=4.0, lower_s=2.0, backend=3, backend_s=11.0, hits=1),
        logged(2, 0.5, trace_s=0.125, lower_s=0.125, backend=1, backend_s=0.0625, hits=1),
        logged(10, 1.99),                                       # the window
        logged(7, 2.0, trace_s=9.0, backend=1, backend_s=9.0)]  # the host capture

WANT = {"setup_import_s.train": 1.5,
        "setup_init_s.train": 3.25,
        "setup_trace_lower_s.train": 6.25,
        "setup_compile_s.train": 11.0625,
        "setup_cold_compiles.train": (7 - 5) + (3 - 1) + (1 - 1),
        "setup_program_s.train": 1.5 + 3.0 + 0.25 + 20.0 + 0.5}


@pytest.fixture()
def program(monkeypatch):
    """A `telemetry.fit_log` and `setup_log` the test fills by hand."""
    fits = []
    monkeypatch.setattr(telemetry, "fit_log", lambda: list(fits))
    monkeypatch.setattr(telemetry, "setup_log", lambda: SETUP)
    return fits


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_the_hand_computed_value(program, name):
    program += FITS
    assert harness.module("metrics", name).read(run_view()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_none_for_a_program_without_setup_log(program, monkeypatch, name):
    program += FITS
    monkeypatch.delattr(telemetry, "setup_log")
    assert harness.module("metrics", name).read(run_view()) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_none_for_fits_without_a_compile_entry(program, name):
    program += [{k: v for k, v in f.items() if k != "compile"} for f in FITS]
    assert harness.module("metrics", name).read(run_view()) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_none_for_a_window_that_matches_no_fit(program, name):
    program += FITS
    assert harness.module("metrics", name).read(run_view(steps=11)) is None
    assert harness.module("metrics", name).read(run_view(window_s=9.0)) is None


def test_every_reader_is_listed_for_every_cell_and_moves_setup_s():
    import json
    import os

    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    listed = {m["name"]: m for m in bench["per_layer"] if m["moves"] == "setup_s"}
    assert sorted(listed) == sorted(READERS)
    for m in listed.values():
        assert m["workloads"] == cells and m["better"] == "lower"
        assert m["name"] in {p["name"] for p in harness.load_cell(cells[0])["per_layer"]}


def test_a_real_fit_log_fills_every_reader(monkeypatch):
    """The readers on the program's own entries: a first fit that compiles
    and a window, as a benchmark run makes them, and the sums hold. (The
    log is cut to this test's fits: an earlier test of the worker may have
    recorded entries by hand, and the readers refuse a log that holds one
    without `compile`.)"""
    pw = _wrapper(width=23)
    rows = _rows(96, seed=5)
    first = _fit(pw, _rows(8), 8)
    window = _fit(pw, rows, 8)
    mine = telemetry.fit_log()[-2:]
    monkeypatch.setattr(telemetry, "fit_log", lambda: list(mine))
    run = NS(counters={"steps": window["steps"], "window_s": window["wall_s"] * 1.01},
             cell={"name": "setup_account_test_cell", "chips": 1})
    got = {n: harness.module("metrics", n).read(run) for n in READERS}
    assert all(v is not None for v in got.values()), got
    assert got["setup_trace_lower_s.train"] >= (first["compile"]["trace_s"]
                                                + first["compile"]["lower_s"])
    parts = sum(got[n] for n in READERS[:4])
    assert 0 < parts <= got["setup_program_s.train"]
