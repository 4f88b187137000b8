"""Per step, the time chip 0 spends in collective operations while nothing
else runs on it."""


def read(run):
    _, runs = run.trace.main_module()
    if not runs or run.cell["chips"] < 2:
        return None
    return 1e3 * run.trace.collective_exposed_s() / len(runs)
