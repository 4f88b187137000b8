"""What every cell's run shares: finding the cell's files by name, the chip
check, the compile counter, the profiler capture and the result line.
"""
from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import os
import shutil
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


# ---------------------------------------------------------------------------
# the cell, found by name
# ---------------------------------------------------------------------------
def _json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """BENCHMARK.json's entry for the cell with its configuration file, its
    traffic file and the metrics that list it."""
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = dict(cells[name])
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cell["cfg"] = _json(os.path.join(ROOT, conf["file"]))
    cell["traffic_params"] = _json(
        os.path.join(HERE, "traffic", cell["traffic"] + ".json"))

    def listed(m):
        return "workloads" not in m or name in m["workloads"]

    cell["end_to_end"] = [m for m in bench["end_to_end"] if listed(m)]
    reported = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [m for m in bench["per_layer"]
                         if listed(m) and m["moves"] in reported]
    return cell


def module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, by file so that a name may hold dots."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    table = _json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SystemExit(f"benchmark/peaks.json has no device kind "
                         f"{device_kind!r}: add it with its source")
    return table["devices"][device_kind]


# ---------------------------------------------------------------------------
# the model and the chip
# ---------------------------------------------------------------------------
def require_model(cfg: dict) -> None:
    """Stop at once, with no result line, when this program cannot build the
    configuration: its zoo has no such class, or the class does not take
    the configuration's arguments. Builds `zoo.<class>(**args)` — no array,
    no device — so that a parent without the model fails BEFORE the process
    waits for the chip (it took 13-135 s to say so: PERF.md section 7,
    PR 38 (e), PR 40 (f))."""
    if "program" not in cfg:
        return
    name = cfg["program"]["zoo"]
    try:
        from benchmark import program

        program.model(cfg)
    except (ImportError, AttributeError, TypeError, ValueError) as e:
        raise SystemExit(f"benchmark: this program cannot build zoo.{name} "
                         f"as the configuration gives it: {type(e).__name__}: {e}")


def require_chips(chips: int) -> dict:
    """Exit non-zero, with no result line, unless JAX's devices are exactly
    the TPU chips the cell asks for. No fallback, no smaller size."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu" or len(devs) != chips:
        raise SystemExit(
            f"benchmark: this cell needs {chips} TPU chip(s); JAX reports "
            f"{len(devs)} x {d0.platform}:{d0.device_kind}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak on the fullest chip: the allocator's peak of live buffers plus
    the peak of the region it reserves for the executables' temporaries. On
    this runtime `peak_bytes_in_use` alone leaves the temporaries out (a
    ResNet-50 b128 training step read 0.98 GB in use beside 4.38 GB
    reserved: my chip run, PR 23)."""
    import jax

    def peak(d):
        m = d.memory_stats()
        return int(m["peak_bytes_in_use"]) + int(m.get("peak_bytes_reserved", 0))

    return max(peak(d) for d in jax.local_devices())


def memory_stats() -> dict:
    import jax

    return {str(d): d.memory_stats() for d in jax.local_devices()}


def enable_compile_cache() -> str:
    """The program's own placement (JAX_COMPILATION_CACHE_DIR, else
    <checkout>/.jax_cache), and every program kept, however quickly it
    compiled, so that only a checkout's first run compiles."""
    import jax

    from deeplearning4j_tpu.util import compile_cache

    d = compile_cache.ensure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


class CompileCounter:
    """Counts XLA backend compilations through JAX's own monitoring events
    — a host count that costs the hot path nothing, and the benchmark's own:
    `compiles_in_window` is held to 0 by `correct` whatever the program
    reports. (The program's `compile_count()` reads its compile account,
    gate on or off, since PR 49: the window fit's `compile.backend_compiles`
    is this count's inside twin.)"""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1


# ---------------------------------------------------------------------------
# the clock of set-up
# ---------------------------------------------------------------------------
class Setup:
    """`setup_s` = from the instant the runtime has the chip (`chip_ready`:
    `require_chips` returned) to the first measured step or request, less
    the time spent in the plain reference — its seeded weights, its steps,
    their compiles: every run pays them, but they are the yardstick's cost
    and not the system's — plus what the program was asked for before the
    chip (`early()`: building the configuration, which imports the package).
    The package's import, `init`, placement, tracing, lowering, compiling or
    the cache's read are all inside it.

    `launch_s` = process start to `chip_ready` less those early seconds: the
    interpreter, JAX, libtpu reaching the chip. The launcher's and the
    runtime's, 10.8-17.2 s that swing by themselves (PERF.md section 6,
    PR 49); printed, reported in the result's `device`, no metric."""

    def __init__(self, t0: float):
        self.t0 = t0
        self.t_ready = None
        self.early_s = 0.0
        self.reference_parts = {}

    def mark(self, what: str) -> None:
        print(f"[bench +{time.perf_counter() - self.t0:7.2f}s] {what}", flush=True)

    @contextlib.contextmanager
    def _timed(self, add):
        t = time.perf_counter()
        try:
            yield
        finally:
            add(time.perf_counter() - t)

    def early(self):
        """The program's seconds before the chip is asked for: set-up."""
        def add(s):
            self.early_s += s
        return self._timed(add)

    def chip_ready(self) -> None:
        self.t_ready = time.perf_counter()

    @property
    def launch_s(self) -> float:
        return self.t_ready - self.t0 - self.early_s

    def reference(self, part: str = "steps"):
        """Time spent inside is the reference's, not set-up."""
        def add(s):
            self.reference_parts[part] = self.reference_parts.get(part, 0.0) + s
        return self._timed(add)

    @property
    def reference_s(self) -> float:
        return sum(self.reference_parts.values())

    def setup_s(self, window_start: float) -> float:
        return window_start - self.t_ready + self.early_s - self.reference_s

    def report(self, window_start: float) -> str:
        """One line: both clocks from the same run (the clock before PR 51
        ran from process start and left the seeded weights in; PERF.md's
        set-up table compares them)."""
        new = self.setup_s(window_start)
        old = new + self.launch_s + self.reference_parts.get("weights", 0.0)
        parts = ", ".join(f"{k} {v:.2f}" for k, v in self.reference_parts.items())
        return (f"[bench] launch_s {self.launch_s:.2f} (no metric); reference "
                f"{self.reference_s:.2f}s (not in setup_s: {parts}); setup_s {new:.2f} "
                f"(process start to window less the reference's steps, "
                f"the clock before PR 51: {old:.2f})")


# ---------------------------------------------------------------------------
# the profiler
# ---------------------------------------------------------------------------
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class Capture:
    """jax.profiler around a traced window; the reduction is
    benchmark/trace_reduce.py's. Python call tracing is always off. With
    `host` false the host tracer is off too and the trace holds the device
    alone: on this runtime the host tracer records every tile the host
    re-lays-out for the device (millions of events a second) and slowed the
    ResNet feed six-fold (PERF.md section 6), so the per-layer metrics are
    read from a device-only capture and a second, short capture with the
    host tracer on says what the host was doing in the idle time."""

    def __init__(self, enabled: bool, tag: str, host: bool):
        self.enabled = enabled
        self.host = host
        self.dir = os.path.join(TRACE_DIR, tag + (".host" if host else ""))
        self.window_s = None

    def __enter__(self):
        if self.enabled:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2 if self.host else 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.enabled:
            import jax

            self.window_s = time.perf_counter() - self.t
            jax.profiler.stop_trace()
        return False

    def reduce(self, chips: int):
        if not self.enabled:
            return None
        from benchmark import trace_reduce

        files = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise RuntimeError(f"the profiler wrote no xplane under {self.dir}")
        return trace_reduce.reduce_file(files[-1], chips)


def annotate(name: str):
    """A host span on the profiler's clock (a no-op when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# the result
# ---------------------------------------------------------------------------
def print_checks(rows) -> bool:
    """Every number compared, beside its limit. True when all hold."""
    ok_all = True
    for name, value, limit, ok, note in rows:
        print(f"[check] {name} = {value!r} limit {limit!r} "
              f"{'ok' if ok else 'FAIL'} ({note})", flush=True)
        ok_all = ok_all and ok
    return ok_all


def emit(result: dict) -> None:
    print(json.dumps(result), flush=True)
