"""End-to-end model FLOP/s utilisation of the traced window: operations the
forward and backward passes require (benchmark/flops, nothing recomputed) x
runs of the train step program in the trace, over window x chips x peak.
Not a roofline share: it counts the idle time too."""


def read(run):
    _, runs = run.trace.main_module()
    if not runs:
        return None
    flops = run.flops.step_flops(run.cfg, run.counters["rows_per_step"])
    peak = run.cell["chips"] * run.peaks["bf16_flops_per_s"]
    return 100.0 * flops * len(runs) / run.window_s / peak
