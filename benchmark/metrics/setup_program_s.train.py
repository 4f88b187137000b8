"""All of `setup_s` that is the program's, from the program's own set-up
account (PR 49): the package's import, every network's `init`, the wrapper's
`place`, and the wall time of every fit before the window's (the checked
steps: trace, lower, compile or the cache's read, and the steps themselves).
`setup_s` less this is the launcher's, the runtime's and the benchmark's
(reaching the chip, the seeded weights, building the batches).

`account` is what the five other `setup_*` readers share: the program's
`setup_log()` and the `fit_log()` entries BEFORE the window's fit. None — never
a guess — for a program without `setup_log`, for fits without a `compile`
entry, or when no fit matches the window."""
from benchmark import span_reduce


def account(run):
    """(`setup_log()`, the fits before the window's) or None."""
    from deeplearning4j_tpu import telemetry

    setup_log = getattr(telemetry, "setup_log", None)
    window = span_reduce.fit_entry(run)
    if setup_log is None or window is None:
        return None
    log = telemetry.fit_log()
    pre = log[:log.index(window)]
    if any("compile" not in f for f in pre + [window]):
        return None
    return setup_log(), pre


def pre_compile(run, *keys):
    """The sum of the `compile` entries `keys` over the fits before the
    window's, or None."""
    acc = account(run)
    if acc is None:
        return None
    return sum(f["compile"][k] for f in acc[1] for k in keys)


def read(run):
    acc = account(run)
    if acc is None:
        return None
    setup, pre = acc
    return (setup["import_s"] + setup["init"]["total_s"] + setup["place"]["total_s"]
            + sum(f["wall_s"] for f in pre))
