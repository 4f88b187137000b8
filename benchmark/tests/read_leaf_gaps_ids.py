#!/usr/bin/env python3
"""Every leaf's gap, not only the worst: for a `train_stream_ids` cell on the
chip, the program's and each control's per-leaf `grad_norms` and
`delta_norms` gaps against the reference (`common.leaf_gaps`), written as
JSON lines to `--out` and summarised (the verdict under the reference file's
`LIMITS`, the median, the largest few). What `COMPARISONS` and `LIMITS` are
chosen from when the worst leaf alone does not part the sound runs from a
control. `--controls`: the reference file's operands and "half_batch", on
the first `--control-seeds` seeds (each is another whole reference: 134 s
at 2 x 8192 tokens of `nemotron3nano_train_t8192`); `--control-only` leaves
the program out; `--seq-len` reads at a stated smaller size.

    python3 benchmark/tests/read_leaf_gaps_ids.py --workload <cell> --seeds 1,2 \
        --controls float8_e4m3fn,drop_carry --control-seeds 1 --out chiprun_out/leaf_gaps.jsonl

Not run by the benchmark."""
from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, program  # noqa: E402
from benchmark.reference import common  # noqa: E402


def summary(numbers, want):
    out = {"loss_gap": [abs(a - b) / abs(b) for a, b in zip(numbers["losses"], want["losses"])]}
    for key in ("grad_norms", "delta_norms"):
        gaps = common.leaf_gaps(numbers[key], want[key])
        top = sorted(gaps.items(), key=lambda kv: -kv[1])[:6]
        out[key] = {"median": statistics.median(gaps.values()),
                    "top": [(k, float(f"{v:.3g}")) for k, v in top], "all": gaps}
    return out


def main():
    import jax

    from benchmark.traffic import train_stream as ts
    from benchmark.traffic import train_stream_ids as tsi

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--control-only", action="store_true")
    ap.add_argument("--seq-len", type=int)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    cell = harness.load_cell(a.workload)
    harness.require_chips(cell["chips"])
    harness.enable_compile_cache()
    cfg, traffic = copy.deepcopy(cell["cfg"]), cell["traffic_params"]
    if a.seq_len:
        cfg["input"]["seq_len"] = a.seq_len
    ref_mod = harness.module("reference", cfg["reference"])
    rows, steps = traffic["per_chip_batch"], traffic["check_steps"]

    print(f"{a.workload} at {rows} x {cfg['input']['seq_len']} tokens, limits {ref_mod.LIMITS}",
          flush=True)

    def record(what, seed, numbers, want, t0):
        s = summary(numbers, want)
        checks = common.compare_training(numbers, want, ref_mod.LIMITS, ref_mod.COMPARISONS)
        s["fails"] = [r[0] for r in checks if not r[3]]
        with open(a.out, "a") as f:
            f.write(json.dumps({"what": what, "seed": seed, **s}) + "\n")
        brief = {k: ({"median": float(f"{v['median']:.3g}"), "top": v["top"]}
                     if isinstance(v, dict) else v) for k, v in s.items()}
        print(f"{what} seed {seed} {json.dumps(brief)} ({time.perf_counter() - t0:.0f}s)",
              flush=True)

    for k, seed in enumerate(int(s) for s in a.seeds.split(",")):
        t0 = time.perf_counter()
        batches = tsi.make_batches(cfg, traffic, rows, seed)
        p0 = jax.device_get(ref_mod.init_params(cfg, seed))
        want = tsi.reference_numbers(ref_mod, cfg, p0, {}, batches, steps)
        print(f"REFERENCE seed {seed} ({time.perf_counter() - t0:.0f}s)", flush=True)
        if k < a.control_seeds:
            for op in filter(None, a.controls.split(",")):
                if op == "half_batch":
                    half = [tuple(b_[: rows // 2] for b_ in b) for b in batches]
                    ctl = tsi.reference_numbers(ref_mod, cfg, p0, {}, half, steps)
                else:
                    ctl = tsi.reference_numbers(ref_mod, cfg, p0, {}, batches, steps, op)
                record(f"CONTROL {op}", seed, ctl, want, t0)
        if a.control_only:
            continue
        net = program.build_net(cfg)
        program.install(net, ref_mod, cfg, p0, {})
        log = ts.StepLog()
        net.set_listeners(log)
        pw = program.wrapper(net, 1)
        stream = ts.make_stream([program.dataset(x, y) for x, y, _ in batches], rows)
        got = ts.program_numbers(net, pw, stream, log, ref_mod, cfg, p0, steps)
        record("PROGRAM", seed, got, want, t0)
        del net, pw, stream, p0, batches
        gc.collect()


if __name__ == "__main__":
    main()
