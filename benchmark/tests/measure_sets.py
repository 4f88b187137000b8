#!/usr/bin/env python3
"""Measure a cell as the contract's `bound` rule says: sets of runs with the
same seeds in each set, every run a new process of benchmark/run.py, and for
each end-to-end metric the spread of each set — the distance between the
first and third quartile (`statistics.quantiles(values, n=4)`) as a share of
the median. This parent never touches JAX (a chip belongs to one process).

    python3 benchmark/tests/measure_sets.py --workload <cell> --seconds 30 \
        --seeds 1,2,3,4,5,6 [--sets 2] [--trace-seed 7]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one(workload, seed, seconds, trace):
    t0 = time.time()
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload, "--seed",
         str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    wall = time.time() - t0
    if p.returncode != 0 or not lines or not lines[-1].startswith("{"):
        print(f"RUN FAILED rc={p.returncode} seed={seed}\n{p.stdout[-3000:]}\n"
              f"{p.stderr[-3000:]}", flush=True)
        return None
    r = json.loads(lines[-1])
    checks = [l for l in lines if l.startswith("[check]") or l.startswith("[bench]")]
    marks = " | ".join(l[7:15].strip() + " " + l[17:40] for l in lines
                       if l.startswith("[bench +"))
    print(f"seed {seed} trace {trace} wall {wall:.0f}s correct {r['correct']} "
          f"attempted {r['attempted']} failed {r['failed']} "
          + " ".join(f"{k}={v['value']:.6g}" for k, v in r["metrics"].items())
          + f" mem {r['device']['memory_peak_bytes'] / 2**30:.2f}GiB marks {marks}",
          flush=True)
    if not r["correct"] or trace:
        print("\n".join(checks), flush=True)
    if trace:
        print(json.dumps({k: r[k] for k in ("breakdown", "device") if k in r}),
              flush=True)
    return r


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace-seed", type=int)
    a = ap.parse_args()
    seeds = [int(s) for s in a.seeds.split(",")]
    sets = []
    for k in range(a.sets):
        print(f"--- set {k + 1}", flush=True)
        sets.append([one(a.workload, s, a.seconds, 0) for s in seeds])
    names = sorted({n for st in sets for r in st if r for n in r["metrics"]})
    for n in names:
        for k, st in enumerate(sets):
            vals = [r["metrics"][n]["value"] for r in st if r]
            if n == "setup_s" and k == 0:
                vals = vals[1:]        # the first run of a call may compile
            if len(vals) >= 2:
                print(f"SPREAD {n} set {k + 1}: median {statistics.median(vals):.6g} "
                      f"spread {100 * spread(vals):.3f} % values "
                      + " ".join(f"{v:.6g}" for v in vals), flush=True)
    if a.trace_seed is not None:
        print("--- traced run", flush=True)
        one(a.workload, a.trace_seed, a.seconds, 1)
    bad = sum(1 for st in sets for r in st if not r or not r["correct"])
    print(f"RUNS {sum(len(s) for s in sets)} not-correct-or-failed {bad}", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
