"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals, averaged over the chips) / window."""


def read(run):
    return 100.0 * (1.0 - run.trace.busy_s / run.window_s)
