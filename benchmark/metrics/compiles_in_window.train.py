"""XLA compilations inside the window (want 0), counted by the BENCHMARK's
listener on JAX's own compile events (`harness.CompileCounter`; `correct`
holds it to 0). The program's own count of the same fit is the window
entry's `compile.backend_compiles` in `telemetry.fit_log()` (PR 49)."""


def read(run):
    return run.counters.get("compiles_in_window")
