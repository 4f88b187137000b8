"""Seconds in the program's `init` and `place` spans: the network built and
initialised (parameters the benchmark then replaces) and put on the mesh."""
from benchmark import harness

_setup = harness.module("metrics", "setup_program_s.train")


def read(run):
    acc = _setup.account(run)
    if acc is None:
        return None
    return acc[0]["init"]["total_s"] + acc[0]["place"]["total_s"]
