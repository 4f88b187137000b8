#!/usr/bin/env python3
"""Find the knee of a serving cell once, on the chip: the highest swept rate
at which at least 99 % of the requests meet the deadline and the queue does
not grow. The cell's traffic file then fixes its rate at 0.8 x that.

    python3 benchmark/sweep_knee.py --workload <cell> --seed 1 \
        --rates 100,200,300,400 --seconds 8

One server, warmed once, is driven at each rate in turn for `--seconds`; a
rate's line gives the share that met the deadline, p50/p95 from the due
time, the goodput, how late the generator ran, and how long after the last
due time the last answer came (a queue that grows shows there). Not run by
the benchmark.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.traffic import serve_open_loop as so  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    a = ap.parse_args()
    cell = harness.load_cell(a.workload)
    harness.require_chips(cell["chips"])
    harness.enable_compile_cache()
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import run as run_mod

    args = argparse.Namespace(seed=a.seed, seconds=a.seconds, trace=0)
    ctx = run_mod.context(args, cell, harness.Setup(time.perf_counter()))
    traffic = cell["traffic_params"]
    _, pool, _, server, counts = so.prepare(ctx)
    try:
        for rate in (float(r) for r in a.rates.split(",")):
            b0 = counts.batches
            sched = so.make_schedule(traffic, a.seed, a.seconds, rate=rate)
            rec = so.drive(server, pool, sched, traffic["deadline_s"])
            s = so.summarize(rec, a.seconds, traffic["deadline_s"])
            print(f"RATE {rate:g} req/s: met {100 * s['met_share']:.2f} % "
                  f"p50 {s['serve_p50_ms']:.2f} ms p95 {s['serve_p95_ms']:.2f} ms "
                  f"goodput {s['serve_goodput']:.1f} rows/s "
                  f"rows/batch {s['rows_answered'] / max(1, counts.batches - b0):.2f} "
                  f"generator late p95 {s['generator_late_p95_ms']:.2f} ms "
                  f"last answer +{s['last_done_after_due_s']:.3f}s "
                  f"outcomes {s['outcomes']}", flush=True)
            time.sleep(1.0)     # let the queue drain before the next rate
    finally:
        server.shutdown()


if __name__ == "__main__":
    main()
