"""Seconds the fits before the window waited for the backend: XLA compiling,
or the persistent cache's read where it hit (`backend_compile_s`)."""
from benchmark import harness

_setup = harness.module("metrics", "setup_program_s.train")


def read(run):
    return _setup.pre_compile(run, "backend_compile_s")
