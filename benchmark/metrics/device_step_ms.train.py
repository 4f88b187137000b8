"""Device busy time of chip 0 per run of the train step program in the
traced window."""


def read(run):
    _, runs = run.trace.main_module()
    if not runs:
        return None
    from benchmark.trace_reduce import total

    return total(run.trace.busy[0]) / 1e6 / len(runs)
