#!/usr/bin/env python3
"""Read, on the chip and at a cell's own size, the two numbers every limit
of `correct` is set from (steps 3 to 5 of "How correct is decided"): the
largest gap sound runs of the program give over many seeds, and the smallest
the control gives — the reference computed with its matmul/conv operands
rounded to the nearest precision below the configuration's (float8_e4m3fn
for mixed bf16).

    python3 benchmark/tests/read_limits.py --workload <cell> \
        --seeds 1,2,...  --control-seeds 3 [--operand float8_e4m3fn]

One process reads every seed (set-up is long). Not run by the benchmark.
"""
from __future__ import annotations

import argparse
import gc
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import harness, program  # noqa: E402
from benchmark.reference import common  # noqa: E402


def gaps(rows):
    out = {}
    for name, value, *_ in rows:
        key = "loss_gap" if name.startswith("loss_gap") else name
        out[key] = max(out.get(key, 0.0), value)
    return out


def train(cell, ctx, seeds, n_control, operand, dump_path=None, control_only=False):
    import jax

    from benchmark.traffic import train_stream as ts

    cfg, traffic = cell["cfg"], cell["traffic_params"]
    ref_mod = harness.module("reference", cfg["reference"])
    rows = traffic["per_chip_batch"] * cell["chips"]
    steps = traffic["check_steps"]
    free = dict.fromkeys(ref_mod.LIMITS, float("inf"))
    dump = []
    for k, seed in enumerate(seeds):
        t0 = time.perf_counter()
        batches = ts.make_batches(cfg, traffic, rows, seed)
        p0, s0 = ref_mod.init_params(cfg, seed), ref_mod.init_state(cfg, seed)
        if cell["chips"] > 1:
            p0, s0 = ctx.replicate(p0), ctx.replicate(s0)
        want = ts.reference_numbers(ref_mod, cfg, p0, s0, batches, steps,
                                    place=ctx.place_rows)
        ctl = None
        for op in (operand.split(",") if k < n_control else ()):
            ctl = ts.reference_numbers(ref_mod, cfg, p0, s0, batches, steps,
                                       operand=op, place=ctx.place_rows)
            print(f"CONTROL {op} seed {seed}",
                  gaps(common.compare_training(ctl, want, free, ref_mod.COMPARISONS)),
                  "worst-leaf", gaps(common.compare_training(ctl, want, free))["grad_norm_gap"],
                  flush=True)
        if control_only:
            continue
        net = program.build_net(cfg)
        program.install(net, ref_mod, cfg, p0, s0)
        log = ts.StepLog()
        net.set_listeners(log)
        pw = program.wrapper(net, cell["chips"])
        stream = ts.make_stream([program.dataset(x, y) for x, y, _ in batches], rows)
        p_host = jax.device_get(p0) if cell["chips"] > 1 else p0
        got = ts.program_numbers(net, pw, stream, log, ref_mod, cfg, p_host, steps)
        print(f"PROGRAM seed {seed}", gaps(common.compare_training(got, want, free, ref_mod.COMPARISONS)),
              "worst-leaf", gaps(common.compare_training(got, want, free))["grad_norm_gap"],
              f"losses {got['losses']} ({time.perf_counter() - t0:.0f}s)", flush=True)
        dump.append({"seed": seed, "reference": want, "program": got,
                     "control": ctl})
        del net, pw, stream, p0, s0, p_host, batches
        gc.collect()
    if dump_path:
        import json

        os.makedirs(os.path.dirname(dump_path), exist_ok=True)
        with open(dump_path, "w") as f:
            json.dump(dump, f)


def serve(cell, ctx, seeds, n_control, operand, dump_path=None, control_only=False):
    from benchmark.traffic import serve_open_loop as so

    cfg, traffic = cell["cfg"], cell["traffic_params"]
    for k, seed in enumerate(seeds):
        ctx.seed = seed
        ref_mod, pool, ref_logits, server, _ = so.prepare(ctx)
        try:
            rec = so.drive(server, pool, so.make_schedule(traffic, seed, 4.0),
                           traffic["deadline_s"])
        finally:
            server.shutdown()
        sample = so.sample_requests(rec, traffic["checked_requests"], seed)
        print(f"PROGRAM seed {seed}",
              so.check_answers(rec, sample, ref_logits, float("inf"))[1:5:3],
              so.summarize(rec, 4.0, traffic["deadline_s"])["outcomes"], flush=True)
        for op in (operand.split(",") if k < n_control else ()):
            import jax

            params = ref_mod.init_params(cfg, seed)
            state = jax.jit(lambda p, x: ref_mod.calibrated_state(p, x, cfg))(
                params, pool[:traffic["calibration_rows"]])
            z = so.reference_pool_logits(ref_mod, cfg, params, state, pool, op)
            p = np.exp(z - z.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            for i in sample:
                lo, n = rec["offsets"][i], rec["rows"][i]
                rec["answers"][i] = p[lo:lo + n]
            print(f"CONTROL {op} seed {seed}",
                  so.check_answers(rec, sample, ref_logits, float("inf"))[1:5:3],
                  flush=True)
        gc.collect()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--operand", help="default: the reference file's CONTROL")
    ap.add_argument("--dump", help="write every leaf's norms here (JSON)")
    ap.add_argument("--control-only", action="store_true",
                    help="train cells: skip the program, read the control alone")
    a = ap.parse_args()
    cell = harness.load_cell(a.workload)
    harness.require_chips(cell["chips"])
    harness.enable_compile_cache()
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import run as run_mod

    args = argparse.Namespace(seed=0, seconds=0.0, trace=0)
    ctx = run_mod.context(args, cell, harness.Setup(time.perf_counter()))
    seeds = [int(s) for s in a.seeds.split(",")]
    kind = cell["traffic_params"]["kind"]
    a.operand = a.operand or harness.module(
        "reference", cell["cfg"]["reference"]).CONTROL
    {"train_stream": train, "serve_open_loop": serve}[kind](
        cell, ctx, seeds, a.control_seeds, a.operand, a.dump, a.control_only)


if __name__ == "__main__":
    main()
