"""Seconds the package's own import took (`setup_log()["import_s"]`: the first
to the last line of `deeplearning4j_tpu/__init__.py`; JAX is loaded before)."""
from benchmark import harness

_setup = harness.module("metrics", "setup_program_s.train")


def read(run):
    acc = _setup.account(run)
    return None if acc is None else acc[0]["import_s"]
