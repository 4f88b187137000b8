"""Traffic kind `serve_open_loop`: independent clients sending requests on a
schedule, whatever the server's state, through `InferenceServer`.

The traffic file gives the rate (requests a second, fixed: found once by a
sweep, see benchmark/README.md), the mix of rows per request, the server's
buckets, queue and deadline, and the sizes of the row pool and the checked
sample. Every seed gets the same multiset of request sizes and the same
multiset of inter-arrival gaps (the quantiles of the exponential law of the
rate), in another order: the seed changes the order of the work, never its
amount. One thread submits at the due times, one collects; latency runs
from the instant a request was DUE to the instant its answer is in the
collector's hands, and the generator's own lateness is reported.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import harness, program

FAILED_MS = 1.0e6      # a failed, shed or late request: over any limit


# ---------------------------------------------------------------------------
# the schedule, from the seed
# ---------------------------------------------------------------------------
def make_schedule(traffic: dict, seed: int, seconds: float, rate=None):
    """(due seconds [n], rows [n], pool offsets [n]) of the requests due in
    [0, seconds)."""
    rate = float(rate if rate is not None else traffic["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([int(seed), 2])
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps *= (seconds / gaps.sum())           # the set spans the window exactly
    rng.shuffle(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    sizes, shares = zip(*sorted((int(k), v) for k, v in traffic["rows_mix"].items()))
    counts = np.floor(np.array(shares) * n).astype(int)
    counts[0] += n - counts.sum()
    rows = np.repeat(np.array(sizes), counts)
    rng.shuffle(rows)
    offsets = rng.integers(0, traffic["pool_rows"] - max(sizes) + 1, n)
    return due, rows, offsets


def make_pool(cfg: dict, traffic: dict, seed: int):
    rng = np.random.default_rng([int(seed), 3])
    return rng.standard_normal((traffic["pool_rows"], *cfg["input"]["shape"]),
                               dtype=np.float32)


# ---------------------------------------------------------------------------
# driving the server
# ---------------------------------------------------------------------------
class DispatchCount:
    """Counts the server's batch dispatches and their padded rows, and puts
    each on the profiler's clock. The server keeps no such record; this
    wraps its dispatch callable from outside."""

    def __init__(self, server):
        self.batches = 0
        self.padded_rows = 0
        inner = server._dispatch

        def counted(xp):
            self.batches += 1
            self.padded_rows += int(xp.shape[0])
            with harness.annotate("bench.server_dispatch"):
                return inner(xp)

        server._dispatch = counted


def drive(server, pool, schedule, deadline_s: float):
    """Send the schedule open-loop. Returns per request: due, submit and
    done times (seconds from the window's start; done = nan when it failed),
    the outcome, and the answer of every request that got one."""
    errors = program.serving_errors()
    due, rows, offs = schedule
    n = len(due)
    submit_t = np.full(n, np.nan)
    done_t = np.full(n, np.nan)
    outcome = ["pending"] * n
    answers = [None] * n
    pending = [None] * n
    submitted = threading.Semaphore(0)
    t0 = time.perf_counter()

    def collect():
        for i in range(n):
            submitted.acquire()
            req = pending[i]
            if req is None:
                continue
            try:
                with harness.annotate("bench.wait_result"):
                    out = server.result(req)
            except errors as e:
                outcome[i] = type(e).__name__
                continue
            done_t[i] = time.perf_counter() - t0
            answers[i] = out
            outcome[i] = "ok"

    collector = threading.Thread(target=collect, name="bench-collector")
    collector.start()
    try:
        for i in range(n):
            wait = due[i] - (time.perf_counter() - t0)
            if wait > 0:
                with harness.annotate("bench.wait_until_due"):
                    time.sleep(wait)
            x = pool[offs[i]:offs[i] + rows[i]]
            submit_t[i] = time.perf_counter() - t0
            try:
                with harness.annotate("bench.submit"):
                    pending[i] = server.submit(x)
            except errors as e:
                outcome[i] = type(e).__name__
            submitted.release()
    finally:
        for _ in range(n):      # never leave the collector parked
            submitted.release()
        collector.join()
    return {"due": due, "rows": rows, "offsets": offs, "submit": submit_t,
            "done": done_t, "outcome": outcome, "answers": answers,
            "elapsed": time.perf_counter() - t0}


def summarize(rec, seconds: float, deadline_s: float) -> dict:
    """The end-to-end numbers over ALL requests of the window."""
    lat_ms = (rec["done"] - rec["due"]) * 1e3
    ok = np.array([o == "ok" for o in rec["outcome"]])
    in_time = ok & (lat_ms <= deadline_s * 1e3)
    lat_all = np.where(in_time, lat_ms, FAILED_MS)
    lateness = (rec["submit"] - rec["due"]) * 1e3
    n = len(lat_all)
    return {
        "attempted": n, "failed": int(n - in_time.sum()),
        "serve_p95_ms": float(np.percentile(lat_all, 95)),
        "serve_p50_ms": float(np.percentile(lat_all, 50)),
        "serve_goodput": float(rec["rows"][in_time].sum() / seconds),
        "rows_answered": int(rec["rows"][ok].sum()),
        "met_share": float(in_time.mean()),
        "generator_late_p95_ms": float(np.nanpercentile(lateness, 95)),
        "generator_late_max_ms": float(np.nanmax(lateness)),
        "outcomes": {o: rec["outcome"].count(o) for o in set(rec["outcome"])},
        "last_done_after_due_s": float(np.nanmax(rec["done"]) - rec["due"][-1])
        if ok.any() else float("nan"),
    }


# ---------------------------------------------------------------------------
# correctness: a seeded sample of the answers against the reference
# ---------------------------------------------------------------------------
def answer_gap(served: np.ndarray, ref_logits: np.ndarray) -> float:
    """The widest gap, over the rows, between the served class
    probabilities and the reference's, measured against the reference's
    largest probability of that row."""
    z = ref_logits.astype(np.float64)
    p = np.exp(z - z.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    gap = np.abs(served.astype(np.float64) - p).max(-1) / p.max(-1)
    return float(gap.max()) if np.all(np.isfinite(served)) else float("inf")


def sample_requests(rec, k: int, seed: int):
    """Indices of k answered requests drawn from the seed, the largest
    among them."""
    ok = [i for i, o in enumerate(rec["outcome"]) if o == "ok"]
    if not ok:
        return []
    rng = np.random.default_rng([int(seed), 4])
    pick = set(rng.choice(ok, size=min(k, len(ok)), replace=False).tolist())
    pick.add(max(ok, key=lambda i: rec["rows"][i]))
    return sorted(pick)


def check_answers(rec, sample, ref_logits_pool, limit: float):
    worst, where = 0.0, None
    for i in sample:
        lo, n = rec["offsets"][i], rec["rows"][i]
        g = answer_gap(np.asarray(rec["answers"][i], np.float32),
                       ref_logits_pool[lo:lo + n])
        if not g <= worst:
            worst, where = g, i
    rows = int(sum(rec["rows"][i] for i in sample))
    return ("answer_gap", worst, limit, bool(sample) and worst <= limit,
            f"{len(sample)} requests, {rows} rows; worst request {where}")


def reference_pool_logits(ref_mod, cfg, params, state, pool, operand=None,
                          block=32):
    import jax

    fn = jax.jit(lambda p, s, x: ref_mod.logits_fn(p, s, x, cfg, operand))
    return np.concatenate([np.asarray(fn(params, state, pool[i:i + block]))
                           for i in range(0, len(pool), block)])


# ---------------------------------------------------------------------------
# one run of a cell
# ---------------------------------------------------------------------------
def prepare(ctx):
    """Seeded weights with calibrated running statistics, the reference's
    logits for the row pool, and the warmed server."""
    import jax

    cfg, traffic, setup = ctx.cfg, ctx.traffic, ctx.setup
    ref_mod = harness.module("reference", cfg["reference"])
    pool = make_pool(cfg, traffic, ctx.seed)
    params = ref_mod.init_params(cfg, ctx.seed)
    state = jax.jit(lambda p, x: ref_mod.calibrated_state(p, x, cfg))(
        params, pool[:traffic["calibration_rows"]])
    jax.block_until_ready(state)
    setup.mark("seeded weights and calibrated running statistics on the device")
    with setup.reference():
        ref_logits = reference_pool_logits(ref_mod, cfg, params, state, pool)
    setup.mark(f"reference answered the {len(pool)}-row pool "
               f"({setup.reference_s:.1f}s, not in setup_s)")
    net = program.build_net(cfg)
    program.install(net, ref_mod, cfg, params, state)
    del params, state
    pw = program.wrapper(net, ctx.cell["chips"])
    server = program.server(net, pw.mesh, traffic, pool[:1])
    counts = DispatchCount(server)
    setup.mark(f"server warmed on buckets {traffic['buckets']}")
    # a short unmeasured burst through submit/result, so that the window
    # meets warm threads and a settled admission estimate
    warm = make_schedule(traffic, ctx.seed + 1, traffic["warm_seconds"])
    drive(server, pool, warm, traffic["deadline_s"])
    return ref_mod, pool, ref_logits, server, counts


def run(ctx) -> dict:
    traffic = ctx.traffic
    ref_mod, pool, ref_logits, server, counts = prepare(ctx)
    try:
        seconds = (min(ctx.seconds, traffic["trace_seconds"]) if ctx.trace
                   else ctx.seconds)
        schedule = make_schedule(traffic, ctx.seed, seconds)
        b0, compiles0 = counts.batches, ctx.compiles.count
        with ctx.capture:
            t_open = time.perf_counter()
            rec = drive(server, pool, schedule, traffic["deadline_s"])
        compiled = ctx.compiles.count - compiles0
        snap = server.snapshot()
        if ctx.trace:   # what the host does in the idle time: its own capture
            with ctx.capture_host:
                drive(server, pool, make_schedule(
                    traffic, ctx.seed + 2, traffic["attribution_seconds"]),
                    traffic["deadline_s"])
    finally:
        server.shutdown()
    s = summarize(rec, seconds, traffic["deadline_s"])
    print(f"[bench] generator ran late by p95 {s['generator_late_p95_ms']:.3f} ms, "
          f"max {s['generator_late_max_ms']:.3f} ms; outcomes {s['outcomes']}; "
          f"p50 {s['serve_p50_ms']:.2f} ms; deadline met by {100 * s['met_share']:.2f} %; "
          f"last answer {s['last_done_after_due_s']:.3f}s after the last due time",
          flush=True)
    sample = sample_requests(rec, traffic["checked_requests"], ctx.seed)
    checks = [check_answers(rec, sample, ref_logits, ref_mod.LIMITS["answer_gap"]),
              ("compiles_in_window", compiled, 0, compiled == 0, "")]
    return {
        "checks": checks, "attempted": s["attempted"], "failed": s["failed"],
        "window_start": t_open,
        "values": {"serve_p95_ms": s["serve_p95_ms"],
                   "serve_goodput": s["serve_goodput"]},
        "counters": {"compiles_in_window": compiled,
                     "batches": counts.batches - b0,
                     "rows_answered": s["rows_answered"],
                     "server_latency_p50_s": snap["latency_p50_s"],
                     "summary": s},
    }
