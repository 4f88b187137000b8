#!/usr/bin/env python3
"""`read_limits.py` for `train_stream_ids` cells: on the chip and at the
cell's own size, the gaps sound runs of the program give over many seeds,
and what each control gives — the reference with its product operands
rounded to float8_e4m3fn, the reference file's own broken variants
("drop_carry", "drop_expert" for qwen3_next) and "half_batch" (the second
half of every batch's rows left out). Every line is compared under the
reference file's own `LIMITS` and says whether it would be `correct`.

    python3 benchmark/tests/read_limits_ids.py --workload <cell> \
        --seeds 1,2,... --control-seeds 3 [--operand float8_e4m3fn,drop_carry:1]

(`name:n` reads that control on the first n seeds only: every control is
another whole reference.)

One process reads every seed. Not run by the benchmark.
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

from benchmark import harness, program  # noqa: E402
from benchmark.reference import common  # noqa: E402
from benchmark.tests.read_limits import gaps  # noqa: E402


def main():
    import jax

    from benchmark.traffic import train_stream as ts
    from benchmark.traffic import train_stream_ids as tsi

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--operand", help="comma-separated; default the reference's CONTROL")
    ap.add_argument("--control-only", action="store_true")
    a = ap.parse_args()
    cell = harness.load_cell(a.workload)
    harness.require_chips(cell["chips"])
    harness.enable_compile_cache()
    cfg, traffic = cell["cfg"], cell["traffic_params"]
    ref_mod = harness.module("reference", cfg["reference"])
    rows, steps = traffic["per_chip_batch"], traffic["check_steps"]
    operands = [(op.split(":")[0], int(op.split(":")[1]) if ":" in op else a.control_seeds)
                for op in (a.operand or ref_mod.CONTROL).split(",")]

    def verdict(numbers, want):
        rows_ = common.compare_training(numbers, want, ref_mod.LIMITS, ref_mod.COMPARISONS)
        return (f"correct {all(r[3] for r in rows_)} fails {[r[0] for r in rows_ if not r[3]]} "
                f"{gaps(rows_)} limits {ref_mod.LIMITS} {[r[4] for r in rows_ if 'norm' in r[0]]}")

    for k, seed in enumerate(int(s) for s in a.seeds.split(",")):
        t0 = time.perf_counter()
        batches = tsi.make_batches(cfg, traffic, rows, seed)
        p0 = jax.device_get(ref_mod.init_params(cfg, seed))
        want = tsi.reference_numbers(ref_mod, cfg, p0, {}, batches, steps)
        print(f"REFERENCE seed {seed} losses {want['losses']} "
              f"({time.perf_counter() - t0:.0f}s)", flush=True)
        for op in (op for op, n in operands if k < n):
            if op == "half_batch":
                half = [tuple(a_[: rows // 2] for a_ in b_) for b_ in batches]
                ctl = tsi.reference_numbers(ref_mod, cfg, p0, {}, half, steps)
            else:
                ctl = tsi.reference_numbers(ref_mod, cfg, p0, {}, batches, steps, op)
            print(f"CONTROL {op} seed {seed}", verdict(ctl, want), flush=True)
        if a.control_only:
            continue
        net = program.build_net(cfg)
        program.install(net, ref_mod, cfg, p0, {})
        log = ts.StepLog()
        net.set_listeners(log)
        pw = program.wrapper(net, 1)
        stream = ts.make_stream([program.dataset(x, y) for x, y, _ in batches], rows)
        got = ts.program_numbers(net, pw, stream, log, ref_mod, cfg, p0, steps)
        print(f"PROGRAM seed {seed}", verdict(got, want),
              f"losses {got['losses']} experts {tsi.expert_counters(2)} "
              f"({time.perf_counter() - t0:.0f}s)", flush=True)
        del net, pw, stream, p0, batches
        gc.collect()


if __name__ == "__main__":
    main()
