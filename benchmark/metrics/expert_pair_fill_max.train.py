"""The fullest pair buffer of the window, per cent: the most assignments one
expert-parallel rank had for another in a step over the rows the pair's buffer
holds, largest over the steps and the expert layers (the program's device
counters, a histogram of the steps read once a fit into
`telemetry.fit_log()`, `experts`, to 1/128) — the number `capacity_factor` is
sized on: beyond 100 assignments are dropped. Left out for a program or a
model without the counter."""


def read(run):
    fill = run.counters.get("pair_fill_max")
    return None if fill is None else 100.0 * fill
