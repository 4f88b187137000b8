"""Traffic kind `train_stream`: a training job fed host batches through
`ParallelWrapper.fit`, as users feed it.

The traffic file gives `per_chip_batch`, `distinct_batches` (seeded host
batches cycled, all rows different), `check_steps` (3) and `trace_seconds`;
the configuration's `input` says what a row is (token sequences with dense
one-hot next-token labels, or images with one-hot classes). One wrapper, one
compiled step and one feed serve the three checked steps and the window.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import harness, program
from benchmark.reference import common


# ---------------------------------------------------------------------------
# data from the seed
# ---------------------------------------------------------------------------
def make_batches(cfg: dict, traffic: dict, rows: int, seed: int):
    """`distinct_batches` host batches as (features, one-hot float32
    labels, integer labels). The program is fed the first two, as a user of
    the DL4J API feeds it; the reference reads the integers."""
    rng = np.random.default_rng([int(seed), 1])
    spec = cfg["input"]
    out = []
    for _ in range(traffic["distinct_batches"]):
        if spec["kind"] == "tokens":
            t, v = spec["seq_len"], spec["vocab"]
            ids = rng.integers(0, v, (rows, t), dtype=np.int64)
            idx = np.roll(ids, -1, axis=1).astype(np.int32)
            x = ids.astype(np.int32)
            y = np.zeros((rows, t, v), np.float32)
            np.put_along_axis(y, idx[..., None], 1.0, axis=-1)
        elif spec["kind"] == "images":
            x = rng.standard_normal((rows, *spec["shape"]), dtype=np.float32)
            idx = rng.integers(0, spec["classes"], rows).astype(np.int32)
            y = np.zeros((rows, spec["classes"]), np.float32)
            y[np.arange(rows), idx] = 1.0
        else:
            raise ValueError(f"unknown input kind {spec['kind']!r}")
        out.append((x, y, idx))
    return out


def make_stream(datasets, rows: int):
    """A `DataSetIterator` that cycles the host batches and stops at a step
    count or a deadline, keeping its place from one `fit` to the next."""
    Base = program.iterator_base()

    class Stream(Base):
        def __init__(self):
            self.position = 0
            self.stop_after = 0
            self.deadline = None

        def arm(self, steps=None, deadline=None):
            self.stop_after = None if steps is None else self.position + steps
            self.deadline = deadline

        def reset(self):
            pass  # a stream has no epoch to rewind

        def __next__(self):
            if self.stop_after is not None and self.position >= self.stop_after:
                raise StopIteration
            if self.deadline is not None and time.perf_counter() >= self.deadline:
                raise StopIteration
            with harness.annotate("bench.next_batch"):
                ds = datasets[self.position % len(datasets)]
            self.position += 1
            return ds

        def batch_size(self):
            return rows

    return Stream()


class StepLog:
    """Listener: the host time and loss at which each step was done."""

    def __init__(self):
        self.times, self.losses = [], []

    def iteration_done(self, model, iteration, score):
        self.times.append(time.perf_counter())
        self.losses.append(float(score))

    def __getattr__(self, name):  # every other listener hook: nothing
        if name.startswith("on_"):
            return lambda *a, **k: None
        raise AttributeError(name)


# ---------------------------------------------------------------------------
# the pieces a test can drive off-chip
# ---------------------------------------------------------------------------
def reference_numbers(ref_mod, cfg, params0, state0, batches, steps,
                      operand=None, place=lambda a: a):
    """The reference's first `steps` steps on the cell's own batches."""
    seq = [(place(batches[i % len(batches)][0]),
            place(batches[i % len(batches)][2])) for i in range(steps)]
    return common.train_steps(ref_mod, cfg, params0, state0, seq, operand)


def program_numbers(net, pw, stream, log, ref_mod, cfg, params0, steps):
    """Drive the wrapper through its first `steps` steps with the window's
    own call and feed, and read what the comparison needs: each step's
    loss, the first gradient as the optimizer got it (from its state after
    one step) and the parameters' change after the last."""
    import jax.numpy as jnp

    opt = ref_mod.optimizer(cfg)
    stream.arm(steps=1)
    pw.fit(stream, epochs=1)
    scale = opt.first_gradient_scale()
    slot = program.read_opt_slot(net, ref_mod, cfg, opt.slot)
    grad_norms = {k: abs(scale) * float(jnp.linalg.norm(v.astype(jnp.float32).ravel()))
                  for k, v in slot.items()}
    stream.arm(steps=steps - 1)
    pw.fit(stream, epochs=1)
    now = program.read_params(net, ref_mod, cfg)
    delta = {k: float(jnp.linalg.norm((now[k] - params0[k]).astype(jnp.float32).ravel()))
             for k in now}
    return {"losses": list(log.losses[:steps]), "grad_norms": grad_norms,
            "delta_norms": delta}


def step_report(times, t_open: float) -> dict:
    """The window's steps by the host clock of their listeners: the median
    interval, the three longest with their step numbers, and the program's
    own phase account of the fit (calls, total and longest of each). A stall
    of the process shows as a rate that fell beside a median that did not
    (`step_wall_median_ms.train` reads `median_s`)."""
    from deeplearning4j_tpu import telemetry

    gaps = np.diff(np.concatenate([[t_open], times]))
    longest = np.argsort(-gaps)[:3]
    log = getattr(telemetry, "fit_log", None)
    phases = log()[-1].get("phases", {}) if log and log() else {}
    return {"steps": len(gaps),
            "median_s": round(float(np.median(gaps)), 4) if len(gaps) else None,
            "longest_s": {int(i) + 1: round(float(gaps[i]), 4) for i in longest},
            "phases": {k: (v["calls"], round(v["total_s"], 3), round(v["max_s"], 4))
                       for k, v in phases.items()}}


def window_counters(n, rows, elapsed, compiled, steps: dict) -> dict:
    """What the per-layer readers get of a window, either driver's."""
    out = {"steps": n, "rows_per_step": rows, "window_s": elapsed,
           "compiles_in_window": compiled}
    if steps["median_s"] is not None:
        out["step_wall_median_ms"] = 1e3 * steps["median_s"]
    return out


def window(net, pw, stream, log, seconds: float):
    """Fit until the deadline; the window closes when the last step's
    parameters are on the device. Returns (steps, elapsed seconds)."""
    import jax

    n0 = len(log.times)
    t0 = time.perf_counter()
    stream.arm(deadline=t0 + seconds)
    pw.fit(stream, epochs=1)
    jax.block_until_ready(net.params)
    return len(log.times) - n0, time.perf_counter() - t0, t0


# ---------------------------------------------------------------------------
# one run of a cell
# ---------------------------------------------------------------------------
def run(ctx) -> dict:
    import jax

    cell, cfg, traffic, setup = ctx.cell, ctx.cfg, ctx.traffic, ctx.setup
    ref_mod = harness.module("reference", cfg["reference"])
    chips = cell["chips"]
    rows = traffic["per_chip_batch"] * chips
    steps = traffic["check_steps"]

    batches = make_batches(cfg, traffic, rows, ctx.seed)
    setup.mark(f"{len(batches)} host batches of {rows} rows built")
    with setup.reference("weights"):    # the yardstick's initialisers and their compiles
        params0 = ref_mod.init_params(cfg, ctx.seed)
        state0 = ref_mod.init_state(cfg, ctx.seed)
        jax.block_until_ready(params0)
    setup.mark(f"seeded weights on the device "
               f"({setup.reference_s:.1f}s, the reference's: not in setup_s)")

    with setup.reference():
        place = ctx.place_rows
        if chips > 1:
            params0, state0 = ctx.replicate(params0), ctx.replicate(state0)
        want = reference_numbers(ref_mod, cfg, params0, state0, batches,
                                 steps, place=place)
    setup.mark(f"reference followed {steps} steps "
               f"({setup.reference_s:.1f}s, not in setup_s)")

    net = program.build_net(cfg)
    program.install(net, ref_mod, cfg, params0, state0)
    log = StepLog()
    net.set_listeners(log)
    pw = program.wrapper(net, chips)
    datasets = [program.dataset(x, y) for x, y, _ in batches]
    stream = make_stream(datasets, rows)
    if chips > 1:  # compare on one device's copy
        params0 = jax.device_get(params0)
    got = program_numbers(net, pw, stream, log, ref_mod, cfg, params0, steps)
    del params0
    setup.mark(f"program took its first {steps} steps (compiled, warm)")

    seconds = min(ctx.seconds, traffic["trace_seconds"]) if ctx.trace else ctx.seconds
    compiles0 = ctx.compiles.count
    with ctx.capture:
        n, elapsed, t_open = window(net, pw, stream, log, seconds)
    compiled = ctx.compiles.count - compiles0
    steps_seen = step_report(log.times[-n:] if n else [], t_open)
    print(f"[bench] window steps {steps_seen}", flush=True)
    win_losses = log.losses[-n:] if n else []
    if ctx.trace:   # what the host does in the idle time: a capture of its own
        with ctx.capture_host:
            window(net, pw, stream, log, traffic["attribution_seconds"])

    rows_out = common.compare_training(got, want, ref_mod.LIMITS,
                                       ref_mod.COMPARISONS)
    finite = bool(np.all(np.isfinite(log.losses)))
    rows_out.append(("losses_finite", finite, True, finite,
                     f"{len(log.losses)} steps"))
    rows_out.append(("compiles_in_window", compiled, 0, compiled == 0, ""))
    k = len(datasets)
    if n >= 2 * k:
        first, last = float(np.mean(win_losses[:k])), float(np.mean(win_losses[-k:]))
        rows_out.append(("window_loss_fell", last - first, 0.0, last < first,
                         f"mean of first {k} steps {first:.4f}, of last {k} {last:.4f}"))
    return {
        "checks": rows_out,
        "attempted": n, "failed": 0 if finite else int(np.sum(~np.isfinite(win_losses))),
        "window_start": t_open,
        "values": {"train_throughput": n * rows / elapsed},
        "counters": window_counters(n, rows, elapsed, compiled, steps_seen),
    }
