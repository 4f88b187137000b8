"""The window's MEDIAN step by the host's clock: the median interval
between the step listener's calls over the window (`train_stream.step_report`,
printed by every run as `[bench] window steps`). `train_throughput` is the
window's steps over its seconds, so ONE stall of the process (1-4 s: PERF.md
section 7, "What stops a run") moves it by 5-11 %; the median is untouched
by it. A rate that fell beside a median that did not is a stall, not a
slower step. Host clock over the whole window (hundreds of ms and more), not
a step alone."""


def read(run):
    return run.counters.get("step_wall_median_ms")
