"""The window's mean entropy of the exit distribution a token, in nats (0 ..
log of the number of passes; a gate that collapsed onto one pass reads 0):
the output layer's device counter `exit_entropy`, read once a fit into
`telemetry.fit_log()` (`exit`). Left out for a program or a model without
the counter."""
from benchmark import harness

_exit = harness.module("metrics", "exit_expected_passes.train")


def read(run):
    e = _exit.exits(run)
    return None if e is None else e[0]["exit_entropy"]
