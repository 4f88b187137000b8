#!/usr/bin/env python3
"""`read_limits_ids.py` for a `train_stream_ids_mesh` cell: on the chips and at
the cell's own size, what each control of the reference file gives against the
sound reference — its product operands rounded to float8_e4m3fn, and the file's
own broken variants ("drop_rank_back" for mellum2: one expert-parallel rank's
returned rows left out) — compared under the reference file's own `LIMITS`.
Every control is another whole reference, so `--steps` may cut the steps both
sides follow (1: the first loss, the first gradient's norms and one step's
change — what the precision and a lost rank already fail).

    python3 benchmark/tests/read_limits_mesh.py --workload <cell> --seeds 1,2 \
        [--operand float8_e4m3fn,drop_rank_back] [--steps 1]

One process reads every seed. Not run by the benchmark.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.reference import common  # noqa: E402
from benchmark.tests.read_limits import gaps  # noqa: E402


def main():
    import jax

    from benchmark.traffic import train_stream_ids as tsi

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--operand", help="comma-separated; default the reference's CONTROL "
                                      "and its first structural control")
    ap.add_argument("--steps", type=int, help="default: the traffic's check_steps")
    a = ap.parse_args()
    cell = harness.load_cell(a.workload)
    harness.require_chips(cell["chips"])
    harness.enable_compile_cache()
    cfg, traffic = cell["cfg"], cell["traffic_params"]
    ref_mod = harness.module("reference", cfg["reference"])
    rows = traffic["per_chip_batch"] * cell["chips"]
    steps = a.steps or traffic["check_steps"]
    operands = (a.operand.split(",") if a.operand
                else [ref_mod.CONTROL, *ref_mod.CONTROLS[:1]])

    def verdict(numbers, want):
        rows_ = common.compare_training(numbers, want, ref_mod.LIMITS, ref_mod.COMPARISONS)
        return (f"correct {all(r[3] for r in rows_)} fails {[r[0] for r in rows_ if not r[3]]} "
                f"{gaps(rows_)} over limit "
                f"{ {r[0]: round(r[1] / r[2], 2) for r in rows_} } "
                f"{[r[4] for r in rows_ if 'norm' in r[0]]}")

    for seed in (int(s) for s in a.seeds.split(",")):
        t0 = time.perf_counter()
        batches = tsi.make_batches(cfg, traffic, rows, seed)
        p0 = jax.device_get(ref_mod.init_params(cfg, seed))
        want = tsi.reference_numbers(ref_mod, cfg, p0, {}, batches, steps)
        print(f"REFERENCE seed {seed} {steps} steps losses {want['losses']} "
              f"({time.perf_counter() - t0:.0f}s)", flush=True)
        for op in operands:
            t1 = time.perf_counter()
            ctl = tsi.reference_numbers(ref_mod, cfg, p0, {}, batches, steps, op)
            print(f"CONTROL {op} seed {seed} ({time.perf_counter() - t1:.0f}s)",
                  verdict(ctl, want), flush=True)


if __name__ == "__main__":
    main()
