#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process every time. It finds the cell in BENCHMARK.json, its
configuration under benchmark/configs/, its traffic under benchmark/traffic/
and hands the run to the traffic kind's driver. It exits non-zero with no
result line unless JAX's devices are exactly the TPU chips the cell asks for
(no CPU fallback, no smaller size). The last line of standard output is the
result object of the contract; with --trace 0 it carries the cell's
end-to-end metrics, with --trace 1 its per-layer metrics and `breakdown`.
`setup_s` runs from the instant the runtime has the chip (`harness.Setup`);
the seconds before it are `launch_s` in the result's `device`.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def context(args, cell, setup):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import numpy as np

    mesh = Mesh(np.array(jax.devices()), ("data",))

    def place_rows(a):
        """Rows over the chips for the reference (one chip: as is)."""
        if len(jax.devices()) == 1:
            return a
        return jax.device_put(a, NamedSharding(
            mesh, P("data", *([None] * (np.ndim(a) - 1)))))

    def replicate(tree):
        return jax.device_put(tree, NamedSharding(mesh, P()))

    return types.SimpleNamespace(
        cell=cell, cfg=cell["cfg"], traffic=cell["traffic_params"],
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        setup=setup, compiles=harness.CompileCounter(),
        capture=harness.Capture(bool(args.trace), cell["name"], host=False),
        capture_host=harness.Capture(bool(args.trace), cell["name"], host=True),
        place_rows=place_rows, replicate=replicate)


def main(argv=None) -> int:
    args = parse(argv)
    cell = harness.load_cell(args.workload)
    setup = harness.Setup(T0)
    import jax  # noqa: F401  the launcher's seconds, with libtpu below

    with setup.early():
        harness.require_model(cell["cfg"])          # SystemExit: no such model here
    device = harness.require_chips(cell["chips"])   # SystemExit off-chip
    setup.chip_ready()
    peaks = harness.peaks(device["kind"])
    cache = harness.enable_compile_cache()
    setup.mark(f"cell {cell['name']} seed {args.seed} on {device['count']} x "
               f"{device['kind']}; compile cache {cache}")

    ctx = context(args, cell, setup)
    driver = harness.module("traffic", cell["traffic_params"]["kind"])
    out = driver.run(ctx)

    correct = harness.print_checks(out["checks"])
    device["memory_peak_bytes"] = harness.memory_peak_bytes()
    device["launch_s"] = setup.launch_s
    print(f"[bench] memory_stats {harness.memory_stats()}", flush=True)
    values = dict(out["values"])
    values["setup_s"] = setup.setup_s(out["window_start"])
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    units = {m["name"]: m["unit"] for m in cell["end_to_end"] + cell["per_layer"]}
    if not ctx.trace:
        names = [m["name"] for m in cell["end_to_end"]]
        metrics = {n: values[n] for n in names}
    else:
        red = ctx.capture.reduce(cell["chips"])
        device["busy_s"] = red.busy_s
        device["window_s"] = ctx.capture.window_s
        view = types.SimpleNamespace(
            trace=red, window_s=ctx.capture.window_s, counters=out["counters"], cell=cell, cfg=cell["cfg"],
            traffic=cell["traffic_params"], peaks=peaks,
            flops=harness.module("flops", cell["cfg"]["flops"]))
        metrics = {}
        for m in cell["per_layer"]:
            v = harness.module("metrics", m["name"]).read(view)
            if v is not None:
                metrics[m["name"]] = v
        result["breakdown"] = {
            "device_ops": red.device_ops(),
            "idle_gaps": ctx.capture_host.reduce(cell["chips"]).idle_gaps_by_host()}
    result["metrics"] = {n: {"value": float(v), "unit": units[n]}
                         for n, v in metrics.items()}
    result["device"] = device
    print(setup.report(out["window_start"]), flush=True)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
