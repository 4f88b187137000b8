"""XLA compilations inside the window (want 0), counted by the benchmark's
listener on JAX's own compile events."""


def read(run):
    return run.counters.get("compiles_in_window")
