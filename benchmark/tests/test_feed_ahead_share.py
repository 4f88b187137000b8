"""`feed_ahead_share.train` (PR 25): the reader of the fit log's
`staged_ahead` count, on entries put there by hand, on a program whose
entries lack the count (the parent of PR 25: `None`, the metric is left
out), and on the traffic driver's own window at a tiny size."""
from types import SimpleNamespace as NS

import pytest

from benchmark import harness, span_reduce as sr
from benchmark.tests.test_span_reduce import fit, log, run_view  # noqa: F401 (log: fixture)

NAME = "feed_ahead_share.train"


def read(run):
    return harness.module("metrics", NAME).read(run)


def ahead(steps, wall_s, staged_ahead):
    return dict(fit(steps, wall_s, put=(0.1, 1)), staged_ahead=staged_ahead)


def test_share_of_the_windows_steps_that_were_staged_ahead(log):
    log += [ahead(1, 40.0, 0),      # the compile step: nothing ahead of it
            ahead(10, 1.99, 9),     # the window: every step but its first
            ahead(7, 2.0, 6)]       # the host capture
    assert read(run_view()) == pytest.approx(90.0)


def test_a_fit_that_staged_nothing_ahead_reads_zero_not_none(log):
    log.append(ahead(10, 1.99, 0))
    assert read(run_view()) == 0.0


@pytest.mark.parametrize("fits", [
    [],                                   # nothing logged
    [fit(10, 1.99, put=(0.1, 1))],        # a program without the count
    [ahead(9, 1.99, 8)],                  # other step count: not the window
])
def test_none_when_there_is_nothing_to_read(log, fits):
    log += fits
    assert read(run_view()) is None


def test_none_for_a_program_without_fit_log(monkeypatch):
    from deeplearning4j_tpu import telemetry

    monkeypatch.delattr(telemetry, "fit_log", raising=False)
    assert read(run_view()) is None


def test_a_tiny_run_on_the_cpu_stages_all_but_the_first_step_ahead():
    from benchmark.tests import test_correct as tc
    from benchmark.tests import tiny
    from benchmark.traffic import train_stream as ts

    cell = tc.tiny_cell(tiny.gpt2(), tc.TRAIN)
    out = ts.run(tiny.ctx(cell, seed=2 ** 31 + 7, seconds=2.0))
    run = NS(counters=out["counters"], cell=cell)
    steps = out["counters"]["steps"]
    assert sr.fit_entry(run)["staged_ahead"] == steps - 1
    assert read(run) == pytest.approx(100.0 * (steps - 1) / steps)
