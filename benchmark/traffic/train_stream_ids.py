"""Traffic kind `train_stream_ids`: `train_stream`'s run with integer labels.

The same job — host batches through `ParallelWrapper.fit`, one wrapper, one
compiled step and one feed for the three checked steps and the window — fed
as every LM pipeline feeds it: token ids [rows, t] int32 and next-token ids
[rows, t] int32, no one-hot array anywhere. The stream, the step log, the
program's checked steps and the window are `train_stream`'s own.

Two things differ in the set-up, both because a configuration may fill the
chip (626 M float32 parameters with gradient and Adam moments are 10 GB):
the seeded weights go to the HOST as soon as they are made and stay there
while the reference and then the program take their checked steps, and a
reference file may bring its own lean `train_steps` (same result as
`common.train_steps`); both stand inside `setup.reference()`, the weights'
initialisers with their compiles too: they are the yardstick's. A model
with routed experts is also held to `expert_dropped_assignments == 0` over
every fit of the run: the reference drops nothing.
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from benchmark import harness, program
from benchmark.reference import common
from benchmark.traffic import train_stream as ts


def make_batches(cfg: dict, traffic: dict, rows: int, seed: int):
    """`distinct_batches` host batches as (ids, next ids, next ids): the
    program is fed the first two, the reference reads the first and third
    (`train_stream`'s tuple, with the integers in the one-hot's place)."""
    rng = np.random.default_rng([int(seed), 1])
    spec = cfg["input"]
    if spec["kind"] != "tokens":
        raise ValueError(f"train_stream_ids feeds token rows, not {spec['kind']!r}")
    out = []
    for _ in range(traffic["distinct_batches"]):
        ids = rng.integers(0, spec["vocab"], (rows, spec["seq_len"]), dtype=np.int64)
        nxt = np.roll(ids, -1, axis=1).astype(np.int32)
        out.append((ids.astype(np.int32), nxt, nxt))
    return out


def reference_numbers(ref_mod, cfg, params0, state0, batches, steps, operand=None):
    """The reference's first `steps` steps from host weights `params0`."""
    import jax

    seq = [(batches[i % len(batches)][0], batches[i % len(batches)][2])
           for i in range(steps)]
    own = getattr(ref_mod, "train_steps", None)
    if own is not None:
        return own(ref_mod, cfg, params0, state0, seq, operand)
    return common.train_steps(ref_mod, cfg, jax.device_put(params0), state0,
                              seq, operand)


def expert_counters(fits):
    """The `experts` entries of the program's `fit_log()` since `fits`
    fits ago, flattened; [] for a model (or a program) that keeps none."""
    from deeplearning4j_tpu import telemetry

    log = getattr(telemetry, "fit_log", None)
    if log is None:
        return []
    return [e for f in log()[-fits:] for e in f.get("experts", ())]


class HostWatch:
    """What the host did while the window ran, printed by every run so that
    a run that reads low says why: the longest stop of a 10 ms ticker thread
    and when (the interpreter or the whole machine stood still), the garbage
    collector's passes, the process's CPU seconds and the jiffies stolen
    from this machine (`/proc/stat`). Costs one sleeping thread."""

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.stop_s, self.stop_at = 0.0, 0.0
        self.gc_passes, self.gc_s, self.gc_max_s, self._gc_t0 = 0, 0.0, 0.0, None
        self.cpu0, self.steal0 = time.process_time(), self._steal()
        self._done = threading.Event()
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self._tick, daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._done.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)
        self.wall_s = time.perf_counter() - self.t0

    def _tick(self):
        last = time.perf_counter()
        while not self._done.wait(0.01):
            now = time.perf_counter()
            if now - last > self.stop_s:
                self.stop_s, self.stop_at = now - last, last - self.t0
            last = now

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            took = time.perf_counter() - self._gc_t0
            self.gc_passes += 1
            self.gc_s += took
            self.gc_max_s = max(self.gc_max_s, took)

    @staticmethod
    def _steal():
        try:
            with open("/proc/stat") as f:
                return int(f.readline().split()[8])
        except (OSError, IndexError, ValueError):
            return None

    def report(self) -> dict:
        steal = self._steal()
        return {"ticker_longest_stop_s": round(self.stop_s, 4),
                "at_s": round(self.stop_at, 2),
                "gc_passes": self.gc_passes, "gc_s": round(self.gc_s, 4),
                "gc_longest_s": round(self.gc_max_s, 4),
                "process_cpu_s": round(time.process_time() - self.cpu0, 2),
                "steal_jiffies": None if None in (steal, self.steal0) else steal - self.steal0,
                "wall_s": round(self.wall_s, 3)}


def run(ctx) -> dict:
    import jax

    cell, cfg, traffic, setup = ctx.cell, ctx.cfg, ctx.traffic, ctx.setup
    if cell["chips"] != 1:
        raise SystemExit("train_stream_ids: one chip (the reference runs unsharded)")
    ref_mod = harness.module("reference", cfg["reference"])
    rows, steps = traffic["per_chip_batch"], traffic["check_steps"]

    batches = make_batches(cfg, traffic, rows, ctx.seed)
    setup.mark(f"{len(batches)} host batches of {rows} rows built")
    with setup.reference("weights"):    # the yardstick's initialisers and their compiles
        params0 = jax.device_get(ref_mod.init_params(cfg, ctx.seed))
        state0 = ref_mod.init_state(cfg, ctx.seed)
    setup.mark(f"seeded weights made on the device, kept on the host "
               f"({setup.reference_s:.1f}s, the reference's: not in setup_s)")

    with setup.reference():
        want = reference_numbers(ref_mod, cfg, params0, state0, batches, steps)
    setup.mark(f"reference followed {steps} steps "
               f"({setup.reference_s:.1f}s, not in setup_s)")

    net = program.build_net(cfg)
    program.install(net, ref_mod, cfg, params0, state0)
    log = ts.StepLog()
    net.set_listeners(log)
    pw = program.wrapper(net, 1)
    stream = ts.make_stream([program.dataset(x, y) for x, y, _ in batches], rows)
    got = ts.program_numbers(net, pw, stream, log, ref_mod, cfg, params0, steps)
    del params0
    setup.mark(f"program took its first {steps} steps (compiled, warm)")

    seconds = min(ctx.seconds, traffic["trace_seconds"]) if ctx.trace else ctx.seconds
    compiles0 = ctx.compiles.count
    with ctx.capture, HostWatch() as watch:
        n, elapsed, t_open = ts.window(net, pw, stream, log, seconds)
    compiled = ctx.compiles.count - compiles0
    print(f"[bench] window host {watch.report()}", flush=True)
    steps_seen = ts.step_report(log.times[-n:] if n else [], t_open)
    print(f"[bench] window steps {steps_seen}", flush=True)
    win_losses = log.losses[-n:] if n else []
    fits = 3                    # two fits of checked steps, the window
    if ctx.trace:
        with ctx.capture_host:
            ts.window(net, pw, stream, log, traffic["attribution_seconds"])
        fits += 1

    rows_out = common.compare_training(got, want, ref_mod.LIMITS, ref_mod.COMPARISONS)
    finite = bool(np.all(np.isfinite(log.losses)))
    rows_out.append(("losses_finite", finite, True, finite, f"{len(log.losses)} steps"))
    rows_out.append(("compiles_in_window", compiled, 0, compiled == 0, ""))
    k = len(batches)
    if n >= 2 * k:
        first, last = float(np.mean(win_losses[:k])), float(np.mean(win_losses[-k:]))
        rows_out.append(("window_loss_fell", last - first, 0.0, last < first,
                         f"mean of first {k} steps {first:.4f}, of last {k} {last:.4f}"))
    experts = expert_counters(fits)
    if "num_experts" in cfg or experts:
        dropped = sum(e["dropped_assignments"] for e in experts)
        ok = bool(experts) and dropped == 0
        rows_out.append(("expert_dropped_assignments", dropped, 0, ok,
                         f"{len(experts)} layer-fits; fill up to "
                         f"{max((e['capacity_fill'] for e in experts), default=0.0):.3f}"
                         if experts else "the program reported no expert counters"))
    return {
        "checks": rows_out,
        "attempted": n, "failed": 0 if finite else int(np.sum(~np.isfinite(win_losses))),
        "window_start": t_open,
        "values": {"train_throughput": n * rows / elapsed},
        "counters": ts.window_counters(n, rows, elapsed, compiled, steps_seen),
    }
