"""Traffic kind `train_stream_ids_mesh`: `train_stream_ids`'s run over ALL
the chips of the cell — rows = `per_chip_batch` x chips through ONE
`ParallelWrapper(MeshSpec(data=chips))`, one compiled step and one feed for
the three checked steps and the window.

For a configuration whose state no chip holds alone (an expert layer spread
over the chips): the reference brings its own `train_steps`, which lays its
weights over the chips (`reference_numbers` honours it); the program's net is
built and given the seeded weights on the HOST (the CPU backend) — 1.78 B
float32 parameters with Adam's moments are 21 GB — and the wrapper places
them, each leaf as its layer declares it; the comparison reads the program's
parameters whole whatever their layout. `expert_dropped_assignments == 0` is
held over every fit, summed over the ranks.
"""
from __future__ import annotations

import contextlib

import numpy as np

from benchmark import harness, program
from benchmark.reference import common
from benchmark.traffic import train_stream as ts
from benchmark.traffic import train_stream_ids as tsi


def on_host():
    """A context in which what JAX makes lives in host memory: the CPU
    backend's device as the default. Without such a backend: as it is."""
    import jax

    try:
        return jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:
        return contextlib.nullcontext()


def build(cfg, ref_mod, params0, state0, chips: int):
    """(net, wrapper): the program's net with the seeded weights, made on the
    host and left there for the wrapper's first fit to place over `chips`."""
    with on_host():
        net = program.build_net(cfg)
        program.install(net, ref_mod, cfg, params0, state0)
    return net, program.wrapper(net, chips)


def exchange_counters(experts) -> dict:
    """What the exchanging expert layers counted over `experts` (`fit_log()`
    entries): the fullest pair buffer, the busiest rank over the mean, and the
    bytes a chip sends a step over the layers; {} for layers without them."""
    mine = [e for e in experts if "pair_fill_max" in e]
    if not mine:
        return {}
    layers = {e["layer"] for e in mine}
    return {"pair_fill_max": max(e["pair_fill_max"] for e in mine),
            "rank_load_max_over_mean": max(e["rank_load_max_over_mean"] for e in mine),
            "exchange_bytes_per_step": sum(
                max(e["exchange_bytes"] for e in mine if e["layer"] == name) for name in layers)}


def run(ctx) -> dict:
    import jax

    cell, cfg, traffic, setup = ctx.cell, ctx.cfg, ctx.traffic, ctx.setup
    chips = cell["chips"]
    ref_mod = harness.module("reference", cfg["reference"])
    rows, steps = traffic["per_chip_batch"] * chips, traffic["check_steps"]

    batches = tsi.make_batches(cfg, traffic, rows, ctx.seed)
    setup.mark(f"{len(batches)} host batches of {rows} rows built")
    with setup.reference("weights"):    # the yardstick's initialisers and their compiles
        params0 = jax.device_get(ref_mod.init_params(cfg, ctx.seed))
        state0 = ref_mod.init_state(cfg, ctx.seed)
    setup.mark(f"seeded weights made over the chips, kept on the host "
               f"({setup.reference_s:.1f}s, the reference's: not in setup_s)")

    with setup.reference():
        want = tsi.reference_numbers(ref_mod, cfg, params0, state0, batches, steps)
    setup.mark(f"reference followed {steps} steps "
               f"({setup.reference_s:.1f}s, not in setup_s)")

    net, pw = build(cfg, ref_mod, params0, state0, chips)
    setup.mark("program's net built and given the seeded weights on the host")
    log = ts.StepLog()
    net.set_listeners(log)
    stream = ts.make_stream([program.dataset(x, y) for x, y, _ in batches], rows)
    got = ts.program_numbers(net, pw, stream, log, ref_mod, cfg, params0, steps)
    del params0
    setup.mark(f"program took its first {steps} steps (compiled, warm)")

    seconds = min(ctx.seconds, traffic["trace_seconds"]) if ctx.trace else ctx.seconds
    compiles0 = ctx.compiles.count
    with ctx.capture, tsi.HostWatch() as watch:
        n, elapsed, t_open = ts.window(net, pw, stream, log, seconds)
    compiled = ctx.compiles.count - compiles0
    print(f"[bench] window host {watch.report()}", flush=True)
    steps_seen = ts.step_report(log.times[-n:] if n else [], t_open)
    print(f"[bench] window steps {steps_seen}", flush=True)
    win_losses = log.losses[-n:] if n else []
    window_experts = tsi.expert_counters(1)
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
    experts = tsi.expert_counters(fits)
    dropped = sum(e["dropped_assignments"] for e in experts)
    seen = exchange_counters(experts)
    rows_out.append(("expert_dropped_assignments", dropped, 0, bool(experts) and dropped == 0,
                     f"{len(experts)} layer-fits, summed over the ranks; fullest pair buffer "
                     f"{seen.get('pair_fill_max', 0.0):.3f}, busiest rank over the mean "
                     f"{seen.get('rank_load_max_over_mean', 0.0):.3f}"
                     if experts else "the program reported no expert counters"))
    counters = ts.window_counters(n, rows, elapsed, compiled, steps_seen)
    counters.update(exchange_counters(window_experts))
    return {
        "checks": rows_out,
        "attempted": n, "failed": 0 if finite else int(np.sum(~np.isfinite(win_losses))),
        "window_start": t_open,
        "values": {"train_throughput": n * rows / elapsed},
        "counters": counters,
    }
