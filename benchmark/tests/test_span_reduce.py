"""The program's spans, reduced (benchmark/span_reduce.py): exclusive
attribution of chip-0 idle time to the innermost `dl4j.*` span of the fit
thread, on planes built by hand; the readers of the phase account on a
`fit_log()` put there by hand; and every new reader's `None` when there is
nothing to read."""
from types import SimpleNamespace as NS

import pytest

from benchmark import harness, span_reduce as sr

READERS = ("etl_wait_ms.train", "put_ms.train", "dispatch_call_ms.train",
           "score_wait_ms.train", "fit_unspanned_ms.train",
           "feed_bytes_per_step.train", "idle_attributed_share.train",
           "idle_in_score_wait_share.train")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes(fit_events, other_events=()):
    """Chip 0 busy 0..100, 400..500 and 900..1000: idle 100..400 and
    500..900 (700 ns)."""
    ops = [ev("%fusion.1 = f32[8]{0} fusion(...)", s, 100)
           for s in (0, 400, 900)]
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=ops),
        NS(name="XLA Modules", events=[ev("jit_step(1)", s, 100)
                                       for s in (0, 400, 900)])])
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=list(fit_events)),
        NS(name="python", events=list(other_events))])
    return [dev, host, NS(name="/host:metadata", lines=[])]


def test_exclusive_gives_each_instant_to_the_innermost_span():
    segs = sr.exclusive([(0, 100, "a"), (10, 40, "b"), (20, 30, "c"),
                         (50, 60, "b"), (200, 300, "d")])
    assert segs == [(0, 10, "a"), (10, 20, "b"), (20, 30, "c"),
                    (30, 40, "b"), (40, 50, "a"), (50, 60, "b"),
                    (60, 100, "a"), (200, 300, "d")]
    # a child recorded a little past its parent's end is cut there
    assert sr.exclusive([(0, 10, "a"), (5, 12, "b")]) == [
        (0, 5, "a"), (5, 10, "b")]


def test_idle_time_is_attributed_once_to_the_innermost_span():
    fit = [ev("dl4j.step#step_num=1,_r=1#", 90, 420),   # 90..510
           ev("dl4j.put", 100, 100),                     # 100..200
           ev("dl4j.dispatch", 200, 50),                 # 200..250
           ev("dl4j.score_wait", 250, 250),              # 250..500
           ev("dl4j.etl", 520, 80),                      # 520..600
           ev("XlaLinearize", 100, 800),                 # not the program's
           ev("dl4j.step", 700, 250),                    # 700..950
           ev("dl4j.score_wait", 750, 200)]              # 750..950
    other = [ev("dl4j.produce", 100, 800)]               # another thread
    by = sr.attribute_planes(planes(fit, other), chips=1)
    ns = {k: round(v * 1e9) for k, v in by.items()}
    assert ns == {
        "dl4j.put": 100, "dl4j.dispatch": 50,
        "dl4j.score_wait": 150 + 150,      # 250..400 and 750..900
        "dl4j.step": 10 + 50,              # 500..510 and 700..750: self time
        "dl4j.etl": 80,
        "unattributed": 10 + 100,          # 510..520 and 600..700
        "idle": 700}
    assert sum(v for k, v in ns.items() if k != "idle") == ns["idle"]
    assert "dl4j.produce" not in by


def test_no_step_span_no_attribution():
    assert sr.attribute_planes(
        planes([ev("bench.next_batch", 100, 50), ev("dl4j.produce", 0, 9)]),
        chips=1) is None


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
def run_view(steps=10, window_s=2.0, cell="span_reduce_test_cell"):
    return NS(counters={"steps": steps, "window_s": window_s},
              cell={"name": cell, "chips": 1})


def fit(steps, wall_s, **phases):
    return {"path": "ParallelWrapper.fit", "steps": steps, "wall_s": wall_s,
            "compiles": 0,
            "phases": {k: {"calls": steps, "total_s": v[0], "max_s": v[0],
                           "bytes": v[1]} for k, v in phases.items()}}


@pytest.fixture()
def log(monkeypatch):
    """A `telemetry.fit_log` the test fills by hand."""
    from deeplearning4j_tpu import telemetry

    fits = []
    monkeypatch.setattr(telemetry, "fit_log", lambda: list(fits),
                        raising=False)
    return fits


def test_readers_pick_the_fit_that_was_the_window(log):
    full = dict(etl=(0.01, 0), put=(0.1, 5000), dispatch=(0.02, 0),
                score_wait=(1.8, 0), listeners=(0.01, 0), step=(1.95, 0))
    log += [fit(1, 40.0, **full),                      # the compile step
            fit(10, 1.99, **full),                     # the window
            fit(10, 3.1, **full),                      # same steps, not it
            fit(7, 2.0, **full)]                       # the host capture
    run = run_view()
    read = {n: harness.module("metrics", n).read(run) for n in READERS[:6]}
    assert read["etl_wait_ms.train"] == pytest.approx(1.0)
    assert read["put_ms.train"] == pytest.approx(10.0)
    assert read["dispatch_call_ms.train"] == pytest.approx(2.0)
    assert read["score_wait_ms.train"] == pytest.approx(180.0)
    assert read["fit_unspanned_ms.train"] == pytest.approx(
        1e3 * (1.99 - 1.94) / 10)
    assert read["feed_bytes_per_step.train"] == 500
    # the phases and the loop's self time add up to the fit's wall time
    assert sum(read[n] for n in READERS[:5]) + 1.0 == pytest.approx(199.0)


@pytest.mark.parametrize("fits", [
    [],                                                     # nothing logged
    [fit(9, 1.99, put=(0.1, 1))],                           # other step count
    [fit(10, 2.4, put=(0.1, 1))],                           # longer than the window
    [fit(10, 1.2, put=(0.1, 1))],                           # far shorter
    [fit(10, 1.99)],                                        # no such span
])
def test_readers_return_none_not_a_guess(log, fits):
    log += fits
    for name in READERS:
        assert harness.module("metrics", name).read(run_view()) is None, name


def test_readers_return_none_for_a_program_without_fit_log(monkeypatch):
    from deeplearning4j_tpu import telemetry

    monkeypatch.delattr(telemetry, "fit_log", raising=False)
    for name in READERS:
        assert harness.module("metrics", name).read(run_view()) is None, name


def test_unspanned_needs_all_four_phases(log):
    log.append(fit(10, 1.99, etl=(0.01, 0), put=(0.1, 1), dispatch=(0.02, 0)))
    assert harness.module("metrics", "fit_unspanned_ms.train").read(
        run_view()) is None
    assert harness.module("metrics", "put_ms.train").read(
        run_view()) == pytest.approx(10.0)


def test_idle_shares_from_a_capture(monkeypatch):
    by = {"dl4j.score_wait": 0.6, "dl4j.put": 0.3, "unattributed": 0.1,
          "idle": 1.0}
    monkeypatch.setattr(sr, "idle_by_span", lambda run: by)
    assert harness.module("metrics", "idle_attributed_share.train").read(
        run_view()) == pytest.approx(90.0)
    assert harness.module("metrics", "idle_in_score_wait_share.train").read(
        run_view()) == pytest.approx(60.0)


def test_a_tiny_run_on_the_cpu_fills_every_account_reader():
    """The traffic driver's own window at a tiny size, then the readers as
    run.py calls them: six numbers, and the phases with the loop's self
    time add up to the fit's wall time, which lies inside the window."""
    from benchmark.tests import test_correct as tc
    from benchmark.tests import tiny
    from benchmark.traffic import train_stream as ts

    cell = tc.tiny_cell(tiny.gpt2(), tc.TRAIN)
    out = ts.run(tiny.ctx(cell, seed=2 ** 31 + 5, seconds=2.0))
    run = NS(counters=out["counters"], cell=cell)
    got = {n: harness.module("metrics", n).read(run) for n in READERS[:6]}
    assert all(v is not None for v in got.values()), got
    steps, window_s = out["counters"]["steps"], out["counters"]["window_s"]
    rows = tc.TRAIN["per_chip_batch"]
    assert got["feed_bytes_per_step.train"] == rows * 16 * 4 + rows * 16 * 64 * 4
    fit = sr.fit_entry(run)
    listeners = 1e3 * fit["phases"]["listeners"]["total_s"] / steps
    total = sum(got[n] for n in READERS[:5]) + listeners
    assert total == pytest.approx(1e3 * fit["wall_s"] / steps)
    # inside the window, and (fit_entry's own condition) over 0.9 of it;
    # on the chip's 30 s window the two agree to 3 %
    assert 0.9 * window_s <= fit["wall_s"] <= window_s
    assert fit["compiles"] == 0 and fit["path"] == "ParallelWrapper.fit"
