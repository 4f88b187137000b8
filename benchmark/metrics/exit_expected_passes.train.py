"""The passes a token is expected to take under the exit distribution,
sum_s s x mean p_s over the window's steps (1 .. the number of passes): the
output layer's device counter `exit_p`, read once a fit into
`telemetry.fit_log()` (`exit`). Left out for a program or a model without the
counter."""
from benchmark import span_reduce


def exits(run):
    fit = span_reduce.fit_entry(run)
    return (fit or {}).get("exit") or None


def read(run):
    e = exits(run)
    return None if e is None else e[0]["expected_passes"]
