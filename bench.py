"""Benchmark harness — prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Primary metric (BASELINE.md): ResNet-50 synthetic-data training throughput,
images/sec/chip. vs_baseline = value / (3000/16) since the north star is
3000 img/s aggregate on a 16-chip v5e pod (=187.5 img/s/chip).

A default (no --model) run ALSO measures every other BASELINE.md config
(lenet / GravesLSTM / transformer / GEMM) and writes the results to a
BENCH_DETAIL.json sidecar next to this file, so every row of BASELINE.md
has a per-round number and regressions in the non-flagship paths are
visible. Stdout stays the single resnet JSON line (driver contract).

Mirrors the reference's measurement harness design: synthetic batches
(BenchmarkDataSetIterator) + PerformanceListener-style samples/sec
(SURVEY.md §6 / BASELINE.md). Run on the real TPU chip by the driver; also
works on CPU (slowly) for smoke testing.

Usage: python bench.py [--model resnet50|lenet|lstm|transformer|gemm|all] [--batch N] [--iters N]
       python bench.py --smoke                    # tier-1 CPU smoke row
       python bench.py --check-regression OLD NEW # round-over-round gate
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


BASELINE_PER_CHIP = 3000.0 / 16.0  # north-star aggregate / v5e-16 chips

# v5e bf16 systolic-array peak — GEMM vs_baseline is fraction-of-peak (MFU).
V5E_BF16_PEAK_TFLOPS = 197.0

# Conservative measured floors for the non-flagship configs (single v5e
# chip, this harness). BASELINE.md publishes no reference numbers for
# these paths, so vs_baseline is value/floor. The floors date from July
# sessions on a chip shared among users and have not been re-measured on
# this machine (ROADMAP S1 replaces them).
PINNED = {
    "lenet": 400_000.0,        # images/sec, batch 256
    "lstm": 3_000_000.0,       # chars/sec, batch 64 x seq 64
    "transformer": 180_000.0,  # tokens/sec, batch 16 x seq 512, bf16
}


# --------------------------------------------------------------------------
# round-over-round regression gate (pure JSON, runs before any jax import)
# --------------------------------------------------------------------------

# metrics where a LOWER value is the regression direction is the default;
# these substrings mark lower-is-better rows (latency, shed)
_LOWER_IS_BETTER = ("latency", "p99", "p50", "shed", "time_to_stable",
                    "cold_compiles", "spread")


def _bench_rows(doc) -> dict:
    """Flatten any bench artifact into {row_key: value}.

    Accepts all three shapes this harness has ever written:
      * the driver wrapper (BENCH_r0x.json): {"parsed": {metric,value,..}}
      * BENCH_DETAIL.json: {model: {metric,value,..}, "ab": .., ..}
      * a bare row: {"metric": .., "value": ..}
    The serving row additionally contributes its 2x-overload sweep point
    (p99 latency + shed rate — the graceful-degradation guarantees) and
    one `serving_sustained_qps{model=...}` row per fleet-hosted model
    (its `per_model` sub-rows), so `--check-regression` gates each
    hosted model independently."""
    rows = {}

    def add_row(row):
        if not isinstance(row, dict):
            return
        metric, value = row.get("metric"), row.get("value")
        if metric is None or not isinstance(value, (int, float)):
            return
        key = str(metric)
        if row.get("model"):
            # per-model fleet rows gate independently — a regression in
            # one hosted model must not hide behind another's headroom
            key = f"{metric}{{model={row['model']}}}"
        rows[key] = float(value)
        for point in row.get("sweep") or []:
            if not isinstance(point, dict) or point.get("offered_x") != 2.0:
                continue
            if isinstance(point.get("latency_p99_ms"), (int, float)):
                rows[f"{key}.2x.latency_p99_ms"] = \
                    float(point["latency_p99_ms"])
            if isinstance(point.get("shed_rate"), (int, float)):
                rows[f"{key}.2x.shed_rate"] = float(point["shed_rate"])
        for sub in row.get("per_model") or []:
            add_row(sub)

    if isinstance(doc, dict):
        if isinstance(doc.get("parsed"), dict):
            add_row(doc["parsed"])
        elif "metric" in doc:
            add_row(doc)
        else:
            for v in doc.values():
                add_row(v)
    return rows


def check_regression(old_path: str, new_path: str,
                     threshold: float = 0.05, stream=None) -> int:
    """Compare the rows two bench artifacts SHARE; exit status 1 when any
    shared row regressed past `threshold` (relative; absolute fallback
    when the old value is 0, which only rate-style rows hit). Throughput
    rows regress downward, latency/shed rows upward. Rows present in
    only one file are listed but never gate — a new bench must not fail
    the round that introduces it. `stream` redirects the table (the
    end-of-sweep auto-gate prints to stderr so stdout stays the one
    driver-contract JSON line)."""
    stream = stream or sys.stdout
    try:
        with open(old_path) as f:
            old_rows = _bench_rows(json.load(f))
        with open(new_path) as f:
            new_rows = _bench_rows(json.load(f))
    except (OSError, ValueError) as e:
        print(f"check-regression: unreadable input: {e}", file=sys.stderr)
        return 2
    if not old_rows or not new_rows:
        print("check-regression: no comparable rows found", file=sys.stderr)
        return 2
    shared = sorted(set(old_rows) & set(new_rows))
    if not shared:
        print("check-regression: the two files share no rows",
              file=sys.stderr)
        return 2
    print(f"{'metric':<44} {'old':>12} {'new':>12} {'delta':>8}  verdict",
          file=stream)
    failures = 0
    for key in shared:
        old, new = old_rows[key], new_rows[key]
        lower_better = any(s in key.lower() for s in _LOWER_IS_BETTER)
        if old != 0:
            delta = (new - old) / abs(old)
            shown = f"{delta * 100:+.1f}%"
        else:
            delta = new - old  # rate from a zero floor: absolute delta
            shown = f"{delta:+.3g}"
        worse = delta > threshold if lower_better else delta < -threshold
        verdict = "REGRESSED" if worse else "ok"
        failures += worse
        print(f"{key:<44} {old:>12.4g} {new:>12.4g} {shown:>8}  {verdict}",
              file=stream)
    for key in sorted(set(old_rows) ^ set(new_rows)):
        which = "old only" if key in old_rows else "new only"
        print(f"{key:<44} {'—':>12} {'—':>12} {'—':>8}  {which}",
              file=stream)
    print(f"{len(shared)} shared row(s), {failures} regressed "
          f"(threshold {threshold * 100:.0f}%)", file=stream)
    return 1 if failures else 0


def _sync(x):
    """Force completion with a host roundtrip: fetching a scalar to the
    host is an unambiguous execution barrier on every backend."""
    import numpy as np
    np.asarray(x[(0,) * x.ndim])  # one element: full dependency, tiny copy


def _one_hot(ids, n):
    """One-hot without a dense n x n eye intermediate."""
    import numpy as np

    ids = np.asarray(ids)
    out = np.zeros(ids.shape + (n,), np.float32)
    np.put_along_axis(out, ids[..., None], 1.0, axis=-1)
    return out


def _timed_scan_steps(net, x, y, iters: int, tuple_args: bool,
                      donate: bool = True):
    """Time `iters` train steps, measured as a device-compute marginal.

    Each run compiles the steps as ONE lax.scan program, with
    params/state/opt donated so XLA reuses their buffers instead of
    copying. Every jit *call* still pays a fixed host dispatch cost;
    timing a 1x window and a 3x window and differencing cancels it, so
    the returned seconds are device compute for `iters` steps.

    x/y ride as runtime args — closed-over arrays bake into the program as
    constants and bloat the serialized executable.
    tuple_args: ComputationGraph steps take (inputs,), (labels,) tuples;
    MultiLayerNetwork steps take bare arrays.
    donate=False compiles the identical program WITHOUT buffer donation
    (XLA copies the carries instead of aliasing them) — the before-arm
    of the in-session donation A/B."""
    import jax
    import jax.random as jr
    import jax.numpy as jnp
    from functools import partial
    from jax import lax

    if net._train_step is None:
        net._train_step = net._build_train_step()
    k = jr.PRNGKey(0)

    @partial(jax.jit, static_argnums=3,
             donate_argnums=(0, 1, 2) if donate else ())
    def run(params, state, opt, n, x, y):
        def body(carry, i):
            params, state, opt = carry
            args = ((x,), (y,)) if tuple_args else (x, y)
            params, state, opt, score = net._train_step(
                params, state, opt, i, jr.fold_in(k, i), *args, None, None)
            return (params, state, opt), score
        (params, state, opt), scores = lax.scan(
            body, (params, state, opt), jnp.arange(n))
        return params, state, opt, scores[-1]

    def timed(n):
        p, s, o = jax.tree_util.tree_map(
            lambda a: a.copy() if hasattr(a, "copy") else a,
            (net.params, net.state, net.opt_state))
        p, s, o, score = run(p, s, o, n, x, y)  # compile + warm
        _sync(score)
        p, s, o = jax.tree_util.tree_map(
            lambda a: a.copy() if hasattr(a, "copy") else a,
            (net.params, net.state, net.opt_state))
        t0 = time.perf_counter()
        p, s, o, score = run(p, s, o, n, x, y)
        _sync(score)
        return time.perf_counter() - t0

    # The shared chip's throughput can jump mid-measurement (sessions vary
    # ~3x); a speed-up between the 1x and 3x windows can make the marginal
    # NEGATIVE. Any positive marginal is legitimate (dispatch-dominated
    # configs have small-but-correct marginals); retry only the
    # pathological sign flips, then fall back to the raw 3x window
    # (dispatch included — conservative, but finite and positive).
    for _ in range(3):
        t1 = timed(iters)
        t3 = timed(3 * iters)
        dt = (t3 - t1) / 2.0
        if dt > 0:
            return dt
    return t3 / 3.0


def _wall_loop_time(net, x, y, n: int, tuple_args: bool) -> float:
    """Wall seconds for `n` PER-STEP dispatches with a per-step host
    score fetch — the exact K=1 fit-loop pattern (one jit call + one
    float(score) sync per step). `host_overhead_ms` in BENCH_DETAIL rows
    is this wall per-step minus the scan-measured jitted step time: the
    per-step tax the window engine (training/engine.py) amortizes."""
    import jax
    import jax.numpy as jnp
    import jax.random as jr

    if net._train_step is None:
        net._train_step = net._build_train_step()
    args = ((x,), (y,)) if tuple_args else (x, y)
    k = jr.PRNGKey(0)
    p, s, o = jax.tree_util.tree_map(
        lambda a: a.copy() if hasattr(a, "copy") else a,
        (net.params, net.state, net.opt_state))
    # warm: the per-step executable is distinct from the scan program
    p, s, o, sc = net._train_step(p, s, o, jnp.asarray(0), k, *args,
                                  None, None)
    float(sc)
    t0 = time.perf_counter()
    for i in range(n):
        p, s, o, sc = net._train_step(p, s, o, jnp.asarray(i),
                                      jr.fold_in(k, i), *args, None, None)
        float(sc)
    return time.perf_counter() - t0


def _window_loop_time(net, x, y, iters: int, kwin: int, tuple_args: bool):
    """Wall seconds for ~`iters` steps dispatched as K-step windows
    through the ACTUAL engine scan (training.engine.build_window_scan
    over the model's raw step), one np.asarray(scores) host fetch per
    window — the DL4J_TPU_STEP_WINDOW=K fit pattern. Returns
    (seconds, steps_run)."""
    import jax
    import jax.numpy as jnp
    import jax.random as jr
    import numpy as np

    from deeplearning4j_tpu.training import engine as engine_mod

    if net._train_step is None:
        net._train_step = net._build_train_step()
    raw = net._train_step_raw
    if tuple_args:
        def step(p, s, o, it, r, xx, yy, fm, lm):
            return raw(p, s, o, it, r, (xx,), (yy,), None, None)
    else:
        step = raw
    scan = engine_mod.build_window_scan(
        step, kwin, watch_name=f"bench.window_step[{kwin}]")
    # the same batch rides every window slot (runtime args, never baked
    # into the program — the r05 compile-payload lesson)
    window = (jnp.stack([x] * kwin), jnp.stack([y] * kwin), None, None)

    def fresh():
        return jax.tree_util.tree_map(
            lambda a: a.copy() if hasattr(a, "copy") else a,
            (net.params, net.state, net.opt_state))

    p, s, o = fresh()
    p, s, o, rng, scores = scan(p, s, o, jr.PRNGKey(0), jnp.asarray(0),
                                window)  # compile + warm
    np.asarray(scores)
    p, s, o = fresh()
    rng = jr.PRNGKey(0)
    n_windows = max(1, iters // kwin)
    t0 = time.perf_counter()
    for i in range(n_windows):
        p, s, o, rng, scores = scan(p, s, o, rng,
                                    jnp.asarray(i * kwin), window)
        np.asarray(scores)
    return time.perf_counter() - t0, n_windows * kwin


def _window_ab_fields(net, x, y, iters: int, tuple_args: bool,
                      scan_dt: float, kwin: int = 0) -> dict:
    """In-session K=1 vs K=kwin window A/B + the host-overhead column.
    Both arms run in THIS session back to back (BENCH_DETAIL's _note:
    cross-round deltas on the shared chip are noise); k8_vs_k1 >= 1.1 on
    ResNet-50 is the campaign's admission bar for the window engine.
    kwin=0 = auto: K=8 on accelerators (the campaign arm), K=2 on CPU
    smoke runs — a CPU compile of an 8-step ResNet scan costs minutes
    and measures nothing."""
    import jax as _jax

    if kwin <= 0:
        kwin = 8 if _jax.default_backend() != "cpu" else 2
    n_wall = max(3, min(iters, 30))
    t1 = _wall_loop_time(net, x, y, n_wall, tuple_args)
    tk, steps = _window_loop_time(net, x, y, iters, kwin, tuple_args)
    k1 = n_wall / t1
    kk = steps / tk
    wall_ms = t1 / n_wall * 1e3
    jit_ms = scan_dt / iters * 1e3
    return {
        "k": kwin,
        "k1_steps_per_s": round(k1, 3),
        f"k{kwin}_steps_per_s": round(kk, 3),
        f"k{kwin}_vs_k1": round(kk / k1, 3),
        "wall_step_ms": round(wall_ms, 3),
        "jit_step_ms": round(jit_ms, 3),
        "host_overhead_ms": round(max(0.0, wall_ms - jit_ms), 3),
    }


def _prefetch_ab_fields(net, x, y, tuple_args: bool, n: int = 12) -> dict:
    """In-session prefetch on/off A/B: wall seconds for `n` per-step
    dispatches consuming host-produced batches synchronously vs through
    AsyncDataSetIterator with device placement on the PRODUCER thread
    (its `place` hook, datasets/iterators.py). Each batch pays a real
    host-side ETL (a fresh augment copy) so the async arm has work to
    overlap; both arms share one warmed per-step executable, so the
    ratio isolates pipeline overlap, not compilation."""
    import jax
    import jax.numpy as jnp
    import jax.random as jr
    import numpy as np

    from deeplearning4j_tpu.datasets.iterators import AsyncDataSetIterator

    if net._train_step is None:
        net._train_step = net._build_train_step()
    xh, yh = np.asarray(x), np.asarray(y)
    k = jr.PRNGKey(0)

    def etl(i):
        # the per-batch host work a producer thread overlaps with
        # device compute: an augment-style copy of the whole batch
        return (xh + xh.dtype.type((i + 1) * 1e-6), yh.copy())

    def fresh():
        return jax.tree_util.tree_map(
            lambda a: a.copy() if hasattr(a, "copy") else a,
            (net.params, net.state, net.opt_state))

    def one_step(carry, i, xb, yb):
        p, s, o = carry
        args = ((xb,), (yb,)) if tuple_args else (xb, yb)
        p, s, o, sc = net._train_step(p, s, o, jnp.asarray(i),
                                      jr.fold_in(k, i), *args, None, None)
        float(sc)  # the K=1 fit loop's per-step host sync
        return (p, s, o)

    carry = one_step(fresh(), 0, jnp.asarray(xh), jnp.asarray(yh))  # warm

    carry = fresh()
    t0 = time.perf_counter()
    for i in range(n):
        xb, yb = etl(i)
        carry = one_step(carry, i, jnp.asarray(xb), jnp.asarray(yb))
    t_off = time.perf_counter() - t0

    it = AsyncDataSetIterator(
        list(range(n)), queue_size=4,
        place=lambda j: tuple(jnp.asarray(a) for a in etl(j)))
    carry = fresh()
    t0 = time.perf_counter()
    for i, (xb, yb) in enumerate(it):
        carry = one_step(carry, i, xb, yb)
    t_on = time.perf_counter() - t0
    it.shutdown()
    return {
        "prefetch_off_s": round(t_off, 4),
        "prefetch_on_s": round(t_on, 4),
        "prefetch_on_vs_off": round(t_off / t_on, 3),
    }


def _convbn_ab_fields(net, x, y, iters: int, tuple_args: bool) -> dict:
    """In-session DL4J_TPU_PALLAS_CONVBN off/forced A/B at the MODEL
    level: rebuild the full train step under each mode and scan-time it,
    so the number covers the fused epilogue in situ across every conv_bn
    hot block — complementing bench_kernel_ab's isolated convbn shapes.
    Off-accelerator the forced arm would run pallas in interpret mode
    (minutes of python per ResNet step), so CPU runs record a skip
    marker instead of measuring noise."""
    import jax as _jax

    from deeplearning4j_tpu.ops import pallas_kernels as pk

    if _jax.default_backend() == "cpu":
        return {"convbn": "skipped: cpu (interpret-mode pallas epilogue)"}
    key = "DL4J_TPU_PALLAS_CONVBN"
    prev = os.environ.get(key)
    saved = net._train_step, getattr(net, "_train_step_raw", None)
    try:
        os.environ[key] = "1"
        if pk.convbn_mode() != "forced" or not pk.helpers_enabled():
            return {"convbn": "skipped: pallas helpers disabled"}
        net._train_step = None
        dt_on = _timed_scan_steps(net, x, y, iters, tuple_args)
        os.environ.pop(key, None)
        net._train_step = None
        dt_off = _timed_scan_steps(net, x, y, iters, tuple_args)
    finally:
        if prev is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = prev
        net._train_step, net._train_step_raw = saved
    return {
        "convbn_on_step_ms": round(dt_on / iters * 1e3, 3),
        "convbn_off_step_ms": round(dt_off / iters * 1e3, 3),
        "convbn_on_vs_off": round(dt_off / dt_on, 3),
    }


def _fsdp_ab_fields(zm, x, y, iters: int) -> dict:
    """In-session replicated vs fsdp×tp A/B over the SAME zoo config:
    each arm builds a fresh net under a ParallelWrapper mesh and fits
    the same batch. Fields per arm: step time, peak_hbm_bytes, the
    peak's source, and the donated carry bytes (per device). The
    comparison field peak_hbm_bytes uses the per-device RESIDENT
    param+opt shard bytes when the backend has no per-arm allocator
    stats (CPU: none at all; TPU: peak_bytes_in_use is
    process-cumulative, so the second arm's allocator peak would
    inherit the first's) — resident bytes are the term FSDP actually
    shards, deterministic, and arm-isolated. Allocator peaks, where
    present, ride along as `allocator_peak_bytes`. The fsdp arm must
    show strictly lower peak_hbm_bytes: that ordering is the
    tentpole's admission evidence (docs/PERFORMANCE.md)."""
    import jax as _jax
    import numpy as np

    from deeplearning4j_tpu.analysis import donation as don_mod
    from deeplearning4j_tpu.datasets import DataSet, ListDataSetIterator
    from deeplearning4j_tpu.parallel import ParallelWrapper
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning4j_tpu.telemetry import introspect

    devs = _jax.devices()
    n = len(devs)
    if n < 2:
        return {"fsdp": "skipped: single device (no axis to shard over)"}
    tp = 2 if n >= 4 and zm.n_heads % 2 == 0 else 1
    arms = {
        "replicated": MeshSpec(data=n),
        "fsdp": MeshSpec(fsdp=n // tp, model=tp),
    }
    ds = DataSet(np.asarray(x, np.float32), np.asarray(y, np.float32))
    out = {}
    for arm, spec in arms.items():
        net = zm.init()
        pw = ParallelWrapper(net, mesh=build_mesh(spec, devs))
        it_ = ListDataSetIterator(ds, batch=ds.num_examples())
        pw.fit(it_, epochs=1)  # warmup: compile + placement
        t0 = time.perf_counter()
        pw.fit(it_, epochs=1 + iters)  # total-epoch contract: +iters more
        dt = time.perf_counter() - t0
        est = don_mod.audit_model(net).estimates["donation"]
        resident = (est["param_bytes_per_device"]
                    + est["opt_state_bytes_per_device"])
        stats = introspect.hbm_stats()
        alloc = [int(ms.get("peak_bytes_in_use", ms.get("bytes_in_use", 0)))
                 for ms in stats.values()]
        entry = {
            "step_ms": round(dt / iters * 1e3, 3),
            "peak_hbm_bytes": int(resident),
            "peak_hbm_source": "resident_param_opt_shard_bytes",
            "donated_bytes_per_step": int(resident),
            "fsdp_sharded": bool(est["fsdp_sharded"]),
            "mesh": {"data": spec.data, "fsdp": spec.fsdp,
                     "model": spec.model},
        }
        if alloc:
            entry["allocator_peak_bytes"] = max(alloc)
        out[f"fsdp_ab_{arm}"] = entry
    rep, fs = out["fsdp_ab_replicated"], out["fsdp_ab_fsdp"]
    out["fsdp_ab_peak_ratio"] = round(
        fs["peak_hbm_bytes"] / max(rep["peak_hbm_bytes"], 1), 4)
    out["fsdp_ab_step_ratio"] = round(
        fs["step_ms"] / max(rep["step_ms"], 1e-9), 3)
    return out


def _session_ab_fields(net, x, y, iters: int, tuple_args: bool,
                       scan_dt: float, label: str,
                       convbn: bool = False, fsdp_zoo=None):
    """ALL in-session A/B knobs for one training row, through ONE
    guarded call site (shared by the resnet and transformer rows —
    previously duplicated tuple_args twins). Each arm is individually
    guarded: a failing knob records `<knob>: "skipped: <reason>"`
    instead of killing the row. The knobs:
      * window   — K=1 vs K=kwin fit-loop dispatch (_window_ab_fields;
                   K auto-drops to 2 off-accelerator)
      * prefetch — sync consume vs AsyncDataSetIterator producer-thread
                   device placement (_prefetch_ab_fields)
      * donation — donated vs copying scan carries (the scan_dt already
                   measured IS the donated arm; only the copy arm reruns)
      * convbn   — DL4J_TPU_PALLAS_CONVBN off vs forced over the full
                   train step (ResNet rows only — the knob is a conv_bn
                   epilogue; self-skips on cpu)
      * fsdp     — replicated vs fsdp×tp param placement over the same
                   zoo config (_fsdp_ab_fields; transformer rows only —
                   pass the ZooModel via `fsdp_zoo`; self-skips on one
                   device)
    All arms run back to back on the same chip in the same session:
    per BENCH_DETAIL's _note rule these ratios, not cross-round deltas,
    are the campaign's admission evidence."""
    out = {}

    def guarded(tag, fn):
        try:
            out.update(fn() or {})
        except Exception as e:
            out[tag] = f"skipped: {type(e).__name__}: {e}"
            print(f"{label} {tag} ab failed: {e}", file=sys.stderr)

    guarded("window", lambda: _window_ab_fields(
        net, x, y, iters, tuple_args, scan_dt))
    guarded("prefetch", lambda: _prefetch_ab_fields(net, x, y, tuple_args))

    def donation():
        dt_copy = _timed_scan_steps(net, x, y, iters, tuple_args,
                                    donate=False)
        return {
            "donation_step_ms": round(scan_dt / iters * 1e3, 3),
            "no_donation_step_ms": round(dt_copy / iters * 1e3, 3),
            "donation_vs_copy": round(dt_copy / scan_dt, 3),
        }

    guarded("donation", donation)
    if convbn:
        guarded("convbn",
                lambda: _convbn_ab_fields(net, x, y, iters, tuple_args))
    if fsdp_zoo is not None:
        guarded("fsdp",
                lambda: _fsdp_ab_fields(fsdp_zoo, x, y, iters))
    return out or None


def _lenet_fit_workload(samples: int, batch: int):
    """(net, DataSet) for the closed-loop tuner arms: the tuner only
    acts on the ENGINE path (epoch ticks), so these arms fit through
    net.fit rather than the raw scan probes above."""
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.zoo import LeNet

    net = LeNet().init()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((samples, 28, 28, 1)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, samples)]
    return net, DataSet(x, y)


def _armed_tuner(journal_dir: str):
    """Context manager: DL4J_TPU_AUTOTUNE armed with a private journal
    dir, tuner singleton re-created under the gate, everything restored
    (env, overrides, singleton) on exit so no bench arm leaks knobs."""
    import contextlib

    from deeplearning4j_tpu.telemetry import tuner as tuner_mod

    @contextlib.contextmanager
    def cm():
        saved = {k: os.environ.get(k)
                 for k in ("DL4J_TPU_AUTOTUNE", "DL4J_TPU_TUNER_DIR")}
        os.environ["DL4J_TPU_AUTOTUNE"] = "1"
        os.environ["DL4J_TPU_TUNER_DIR"] = journal_dir
        tuner_mod.reset_for_tests()
        try:
            yield tuner_mod
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            tuner_mod.reset_for_tests()

    return cm()


# frame-build p50 budget for the federation smoke: a telemetry frame is
# built once per scrape on EVERY host, so its cost is fleet-wide
# scrape-path overhead; 50ms is ~100x the observed CPU cost — headroom
# for CI noise, but a structural regression (an O(ring) copy turning
# O(ring^2), a registry walk gone quadratic) blows through it
FRAME_BUILD_P50_BUDGET_S = 0.05


def _federation_smoke_fields() -> dict:
    """Smoke assertion for the federation layer: build a batch of
    telemetry frames against the live registry/ring and hold the
    dl4j_tpu_telemetry_frame_build_seconds p50 under budget. ok=False
    fails the smoke like a lint finding."""
    from deeplearning4j_tpu.telemetry import export as export_mod

    exp = export_mod.FrameExporter(host="smoke", replica="-")
    frames = 25
    for _ in range(frames):
        exp.frame()
    p50 = export_mod.build_latency_quantile(0.5)
    return {
        "ok": p50 is not None and p50 <= FRAME_BUILD_P50_BUDGET_S,
        "frames": frames,
        "frame_build_p50_s": p50,
        "budget_s": FRAME_BUILD_P50_BUDGET_S,
    }


def _tuning_smoke_fields() -> dict:
    """Smoke exercise of the closed loop: a tiny engine fit with
    DL4J_TPU_AUTOTUNE armed, reporting whether the tuner ticked and how
    many decisions it journaled. Whether it DECIDES anything within two
    CPU epochs turns on a CPU timing (the host-overhead share), so the
    row reports it and the exit code does not depend on it."""
    import tempfile

    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.tuning import decisions as dec_mod

    jdir = tempfile.mkdtemp(prefix="dl4j-tpu-bench-tuner-")
    net, ds = _lenet_fit_workload(samples=32, batch=8)
    with _armed_tuner(jdir) as tuner_mod:
        net.fit(ListDataSetIterator(ds, batch=8), epochs=2)
        st = tuner_mod.status()
        entries = dec_mod.read_journal(
            path=os.path.join(jdir, "decisions.jsonl"))
    return {
        "enabled": bool(st.get("enabled")),
        "ticks": st.get("ticks", 0),
        "decisions": len(entries),
    }


def _auto_vs_default_fields(samples: int = 256, batch: int = 16,
                            epochs: int = 2) -> dict:
    """In-session closed-loop A/B: the same engine workload fit with
    knobs at declared defaults vs with DL4J_TPU_AUTOTUNE driving them.
    Both arms run back to back in THIS session (BENCH_DETAIL's _note
    rule); each arm pays its compiles in an untimed convergence pass —
    the auto arm's pass also lets the tuner walk the knobs to its fixed
    point, so the timed pass measures the converged config, not the
    search. The ratio is the acceptance row: auto >= default means the
    controller found (at least) the hand-tuned config on its own."""
    import tempfile
    import time as time_mod

    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.tuning import decisions as dec_mod

    def timed_fit(net, ds):
        t0 = time_mod.perf_counter()
        net.fit(ListDataSetIterator(ds, batch=batch), epochs=epochs)
        return time_mod.perf_counter() - t0

    # default arm
    net, ds = _lenet_fit_workload(samples, batch)
    net.fit(ListDataSetIterator(ds, batch=batch), epochs=1)  # compiles
    t_default = timed_fit(net, ds)

    # auto arm: fresh params, same data; convergence pass untimed
    jdir = tempfile.mkdtemp(prefix="dl4j-tpu-bench-tuner-")
    net2, ds2 = _lenet_fit_workload(samples, batch)
    with _armed_tuner(jdir) as tuner_mod:
        net2.fit(ListDataSetIterator(ds2, batch=batch), epochs=3)
        t_auto = timed_fit(net2, ds2)
        st = tuner_mod.status()
        overrides = dict(st.get("overrides") or {})
    n_dec = len(dec_mod.read_journal(
        path=os.path.join(jdir, "decisions.jsonl")))
    steps = (samples // batch) * epochs
    return {
        "metric": "auto_vs_default_speedup",
        "value": round(t_default / t_auto, 3) if t_auto > 0 else 0.0,
        "unit": "x (>=1.0 means the tuner matched/beat defaults)",
        "default_images_per_sec": round(steps * batch / t_default, 2),
        "auto_images_per_sec": round(steps * batch / t_auto, 2),
        "decisions": n_dec,
        "converged_overrides": overrides,
    }


def bench_resnet50(batch: int, iters: int, mixed: bool = True):
    """ResNet-50 training img/s. `mixed` (default): bf16 activations / f32
    params+stats+loss (dtypes.set_mixed_precision)."""
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu import dtypes
    from deeplearning4j_tpu.zoo import ResNet50

    dtypes.set_mixed_precision(mixed)
    net = ResNet50(num_classes=1000, input_shape=(224, 224, 3)).init()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, 224, 224, 3),
                                        dtype=np.float32))
    if mixed:
        # feed bf16 images: the first conv casts anyway under the policy,
        # and bf16 halves the input-reread traffic of the conv1 wgrad
        x = x.astype(jnp.bfloat16)
    y = jnp.asarray(_one_hot(rng.integers(0, 1000, batch), 1000))
    dt = _timed_scan_steps(net, x, y, iters, tuple_args=True)
    # achieved-vs-peak accounting for the flagship config (telemetry/
    # profiler.py): XLA cost_analysis of the fitted step over the
    # measured per-step marginal; best-effort — the throughput number
    # must survive any cost-model failure
    mfu = None
    try:
        from deeplearning4j_tpu.telemetry import profiler

        mfu = profiler.step_mfu(net, x, y, dt / iters,
                                dtype="bf16" if mixed else "f32")
    except Exception as e:
        print(f"resnet50 mfu estimate failed: {e}", file=sys.stderr)
    # in-session four-knob A/B (window K, prefetch, donation, convbn) +
    # host_overhead_ms — best-effort per arm: the headline number must
    # survive any A/B failure
    wab = _session_ab_fields(net, x, y, iters, tuple_args=True,
                             scan_dt=dt, label="resnet50", convbn=True)
    return batch * iters / dt, mfu, wab


def bench_lenet(batch: int, iters: int):
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.zoo import LeNet

    net = LeNet().init()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, 28, 28, 1), dtype=np.float32))
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)])
    dt = _timed_scan_steps(net, x, y, iters, tuple_args=False)
    return batch * iters / dt


def bench_lstm(batch: int, iters: int, seq_len: int = 64):
    """GravesLSTM char-RNN training throughput (BASELINE config #3:
    TextGenerationLSTM, LSTMHelpers/CudnnLSTMHelper path -> lax.scan +
    pallas cell). Reports characters/sec (= batch * seq_len * steps / s)."""
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.zoo import TextGenerationLSTM

    zm = TextGenerationLSTM(max_length=seq_len)
    net = zm.init()
    vocab = zm.num_classes
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (batch, seq_len))
    x = jnp.asarray(_one_hot(ids, vocab))
    y = jnp.asarray(_one_hot(np.roll(ids, -1, axis=1), vocab))
    dt = _timed_scan_steps(net, x, y, iters, tuple_args=False)
    return batch * seq_len * iters / dt


def bench_transformer(batch: int, iters: int, seq_len: int = 512,
                      mixed: bool = True):
    """TransformerLM training throughput, tokens/sec (net-new capability —
    the reference is pre-transformer; this is the long-context path the
    ring-attention/sp design feeds)."""
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu import dtypes
    from deeplearning4j_tpu.zoo import TransformerLM

    dtypes.set_mixed_precision(mixed)
    zm = TransformerLM(num_classes=8192, max_length=seq_len, d_model=512,
                       n_heads=8, n_layers=6)
    net = zm.init()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 8192, (batch, seq_len))
    x = jnp.asarray(ids, jnp.int32)
    y = jnp.asarray(_one_hot(np.roll(ids, -1, 1), 8192))
    dt = _timed_scan_steps(net, x, y, iters, tuple_args=False)
    # in-session window/prefetch/donation A/B + host_overhead_ms, same
    # best-effort posture as the resnet row (no convbn — no conv_bn
    # blocks in a TransformerLM)
    wab = _session_ab_fields(net, x, y, iters, tuple_args=False,
                             scan_dt=dt, label="transformer",
                             fsdp_zoo=zm)
    return batch * seq_len * iters / dt, wab


def bench_gemm(size: int = 16384, iters: int = 30):
    """MXU utilization probe: bf16 GEMM TFLOPS/chip. The matmul chain runs
    inside ONE compiled fori_loop so per-call host dispatch is paid once.
    Size 16384 (0.5 GB/operand):
    smaller GEMMs under-fill the MXU pipeline on a loop-carried chain
    (4096 reads ~81 TFLOPS, 16384 ~166 on the same chip)."""
    import jax
    import jax.numpy as jnp
    from functools import partial
    from jax import lax

    a = jnp.ones((size, size), jnp.bfloat16)

    @partial(jax.jit, static_argnums=1)
    def chain(a, n):
        def body(_, c):
            return jnp.matmul(a, c, preferred_element_type=jnp.float32
                              ).astype(jnp.bfloat16)
        return lax.fori_loop(0, n, body, a)

    def timed(n):
        c = chain(a, n)  # compile + warm
        _sync(c)
        t0 = time.perf_counter()
        c = chain(a, n)
        _sync(c)
        return time.perf_counter() - t0

    # difference a 1x and a 3x chain to cancel the fixed per-call
    # dispatch overhead
    dt = (timed(3 * iters) - timed(iters)) / 2.0
    flops = 2 * size ** 3 * iters
    return flops / dt / 1e12


def _ab_window(step, args0, iters: int):
    """Median-of-3 long-window marginal per step (seconds). Long windows
    (>=100 iters) are required: short windows flip verdicts under the
    shared chip's contention bursts (round-3 finding, docs/DEVNOTES.md)."""
    import statistics

    import jax
    import jax.numpy as jnp
    from functools import partial
    from jax import lax

    @partial(jax.jit, static_argnums=1, donate_argnums=0)
    def run(a, m):
        def body(carry, i):
            return step(carry, i), 0.0
        carry, _ = lax.scan(body, a, jnp.arange(m))
        return carry

    def timed(m):
        a = jax.tree_util.tree_map(jnp.copy, args0)
        a = run(a, m)
        _sync(jax.tree_util.tree_leaves(a)[0])
        a = jax.tree_util.tree_map(jnp.copy, args0)
        t0 = time.perf_counter()
        a = run(a, m)
        _sync(jax.tree_util.tree_leaves(a)[0])
        return time.perf_counter() - t0

    vals = []
    for _ in range(3):
        t1, t3 = timed(iters), timed(3 * iters)
        if t3 > t1:
            vals.append((t3 - t1) / (2.0 * iters))
    return statistics.median(vals) if vals else timed(3 * iters) / (3 * iters)


def bench_kernel_ab(on_tpu: bool) -> dict:
    """In-session pallas-kernel vs XLA-builtin A/B per helper, written to
    BENCH_DETAIL['ab'] each round so 'kernel X is worth it' is recorded
    machine-readably, not as a DEVNOTES anecdote. These A/Bs set the
    round-3 admission policy (LSTM kernels opt-in; flash auto at
    t >= 1024).

    Every A/B entry is individually guarded: one kernel shape failing
    records a per-entry "skipped: <reason>" instead of killing the whole
    sweep."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.ops import attention as att
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    rng = np.random.default_rng(0)
    iters = 100 if on_tpu else 2
    out = {}

    def entry(tag, tk, tx):
        out[tag] = {"kernel_ms": round(tk * 1e3, 4),
                    "xla_ms": round(tx * 1e3, 4),
                    "kernel_vs_xla": round(tx / tk, 3)}

    def guarded(tag, fn):
        """Run one A/B; a failure (payload limit, OOM, interpreter gap)
        becomes a machine-readable skip, never a sweep-wide crash."""
        try:
            fn()
        except Exception as e:
            out[tag] = {"skipped": f"{type(e).__name__}: {e}"}

    # --- fused LSTM fwd+bwd vs lax.scan at the char-RNN bench shape
    b, t, n = (64, 64, 256) if on_tpu else (16, 8, 16)
    zx0 = jnp.asarray(rng.standard_normal((b, t, 4 * n)) * 0.2, jnp.float32)
    R0 = jnp.asarray(rng.standard_normal((n, 4 * n)) * 0.05, jnp.float32)
    h0 = jnp.zeros((b, n), jnp.float32)
    c0 = jnp.zeros((b, n), jnp.float32)
    bb = pk.pick_lstm_block(zx0.shape, jnp.float32)
    interp = not on_tpu

    def lstm_step(fn):
        def loss(zx, R):
            hs, hT, cT = fn(zx, R)
            return ((hs * hs).sum() + hT.sum()).astype(jnp.float32)

        def step(carry, i):
            import jax as _j
            zx, R = carry
            dzx, dR = _j.grad(loss, argnums=(0, 1))(zx, R)
            return (zx - (1e-4 * dzx).astype(zx.dtype),
                    R - (1e-4 * dR).astype(R.dtype))
        return step

    if bb:  # 0 = the picker says the kernel won't fit: nothing to A/B
        def _ab_lstm():
            tk = _ab_window(lstm_step(
                lambda zx, R: pk.lstm_scan(zx, R, h0, c0, bb, interp)),
                (zx0, R0), iters)
            tx = _ab_window(lstm_step(
                lambda zx, R: pk._lstm_ref(zx, R, h0, c0)), (zx0, R0),
                iters)
            entry(f"lstm_f32_b{b}_t{t}_n{n}", tk, tx)

        guarded(f"lstm_f32_b{b}_t{t}_n{n}", _ab_lstm)

    # --- LSTM long-t / small-b regime (round-3 verdict item 9, CLOSED
    # round 5): the full-t kernel could never fit here (one 8-row block
    # over the VMEM budget), so round 4 recorded the regime as
    # unreachable-by-design. The time-chunked kernels
    # (pk.lstm_scan_chunked — zx/hs streamed per chunk, carries in
    # scratch, boundary checkpoints for the chunked-BPTT backward) now
    # reach it and are AUTO-admitted for f32 at t >= 1024; this A/B is
    # the per-round evidence behind that admission.
    for (b2, t2, n2) in ([(8, 1024, 256), (8, 4096, 256)] if on_tpu
                         else [(8, 32, 16)]):
        zc = jnp.asarray(rng.standard_normal((b2, t2, 4 * n2)) * 0.2,
                         jnp.float32)
        Rc = jnp.asarray(rng.standard_normal((n2, 4 * n2)) * 0.05,
                         jnp.float32)
        hc = jnp.zeros((b2, n2), jnp.float32)
        cc = jnp.zeros((b2, n2), jnp.float32)
        planc = pk.pick_lstm_chunk(zc.shape, jnp.float32)
        if not planc:
            out[f"lstm_chunked_f32_b{b2}_t{t2}_n{n2}"] = {
                "note": "no chunk plan fits — XLA scan only"}
            continue
        cbb, ctc = planc

        def _ab_chunked(zc=zc, Rc=Rc, hc=hc, cc=cc, cbb=cbb, ctc=ctc,
                        tag=f"lstm_chunked_f32_b{b2}_t{t2}_n{n2}"):
            tk = _ab_window(lstm_step(
                lambda zx, R: pk.lstm_scan_chunked(zx, R, hc, cc, cbb,
                                                   ctc, interp)),
                (zc, Rc), iters)
            tx = _ab_window(lstm_step(
                lambda zx, R: pk._lstm_ref(zx, R, hc, cc)), (zc, Rc),
                iters)
            entry(tag, tk, tx)

        guarded(f"lstm_chunked_f32_b{b2}_t{t2}_n{n2}", _ab_chunked)

    # --- flash attention fwd+bwd vs sdpa: short, BOUNDARY (t=1024, the
    # coded admission threshold — round-3 verdict weak #2 flagged that
    # the boundary itself was interpolated, not measured), and long
    # sequence; boundary in both dtypes
    shapes = ([(16, 8, 512, 64, jnp.bfloat16),
               (8, 8, 1024, 64, jnp.bfloat16),
               (8, 8, 1024, 64, jnp.float32),
               (4, 8, 2048, 64, jnp.bfloat16)] if on_tpu else
              [(1, 2, 32, 16, jnp.bfloat16)])
    for (ab_, h_, t_, d_, dt_) in shapes:
        q0, k0, v0 = (jnp.asarray(
            rng.standard_normal((ab_, h_, t_, d_)) * 0.3, dt_)
            for _ in range(3))
        # round 5: the production block picker, not the legacy 128/128
        bq_, bk_ = pk.pick_flash_blocks(t_, d_, dt_)

        def att_step(fn):
            def loss(q, k, v):
                return (fn(q, k, v).astype(jnp.float32) ** 2).sum()

            def step(carry, i):
                import jax as _j
                q, k, v = carry
                dq, dk, dv = _j.grad(loss, argnums=(0, 1, 2))(q, k, v)
                return (q - (1e-4 * dq).astype(q.dtype),
                        k - (1e-4 * dk).astype(k.dtype),
                        v - (1e-4 * dv).astype(v.dtype))
            return step

        # same >=100-iter window floor as the LSTM A/B — shorter windows
        # flip verdicts under contention (the round-2 artifact)
        dt_name = "bf16" if dt_ == jnp.bfloat16 else "f32"

        def _ab_flash(q0=q0, k0=k0, v0=v0, bq_=bq_, bk_=bk_,
                      att_step=att_step,
                      tag=f"flash_{dt_name}_b{ab_}_t{t_}_d{d_}"):
            tk = _ab_window(att_step(lambda q, k, v: pk.flash_attention(
                q, k, v, True, None, bq_, bk_, interp)), (q0, k0, v0),
                iters)
            tx = _ab_window(att_step(lambda q, k, v: att.sdpa(
                q, k, v, causal=True)), (q0, k0, v0), iters)
            entry(tag, tk, tx)

        guarded(f"flash_{dt_name}_b{ab_}_t{t_}_d{d_}", _ab_flash)
    # --- fused linear+xent vs XLA logits+log_softmax at the transformer
    # bench head shape (round-5: the profile's top non-gemm sink). The
    # step differentiates wrt x AND W, so the A/B covers the whole fused
    # stage: fwd online-lse + the two recompute bwd kernels vs XLA's
    # materialized [N,V] logits fwd+bwd.
    from deeplearning4j_tpu.ops import xent_kernel as xk

    for (n_, d_, v_, dt_) in ([(8192, 512, 8192, jnp.bfloat16),
                               (8192, 512, 8192, jnp.float32)] if on_tpu
                              else [(64, 128, 2048, jnp.float32)]):
        x0 = jnp.asarray(rng.standard_normal((n_, d_)) * 0.3, dt_)
        w0 = jnp.asarray(rng.standard_normal((d_, v_)) * 0.05, dt_)
        b0 = jnp.zeros((v_,), jnp.float32)
        t0 = jnp.asarray(
            np.eye(v_, dtype=np.float32)[rng.integers(0, v_, n_)])
        pn = xk.plan(n_, d_, v_, dt_)

        def xent_step(fn):
            # the [n, v] one-hot target rides in the CARRY, not the
            # closure: closed-over arrays bake into the program as
            # constants, and at 8192x8192 f32 that is 256 MB of
            # serialized program. As a runtime arg it never enters it.
            def loss(x, w, t):
                return jnp.sum(fn(x, w, t))

            def step(carry, i):
                import jax as _j
                x, w, t = carry
                dx, dw = _j.grad(loss, argnums=(0, 1))(x, w, t)
                return (x - (1e-4 * dx).astype(x.dtype),
                        w - (1e-4 * dw).astype(w.dtype), t)
            return step

        if pn:
            dt_name = "bf16" if dt_ == jnp.bfloat16 else "f32"

            def _ab_xent(x0=x0, w0=w0, b0=b0, t0=t0, pn=pn,
                         xent_step=xent_step,
                         tag=f"xent_{dt_name}_n{n_}_d{d_}_v{v_}"):
                tk = _ab_window(xent_step(
                    lambda x, w, t: xk.linear_xent_rows(x, w, b0, t, pn,
                                                        interp)),
                    (x0, w0, t0), iters)
                tx = _ab_window(xent_step(
                    lambda x, w, t: xk.linear_xent_reference(x, w, b0,
                                                             t)),
                    (x0, w0, t0), iters)
                entry(tag, tk, tx)

            guarded(f"xent_{dt_name}_n{n_}_d{d_}_v{v_}", _ab_xent)

    # --- fused conv-bn-relu epilogue vs the XLA reference at the ResNet
    # hot-block activation shapes (round-6: the roofline classifies the
    # normalize/affine/relu tail memory-bound; this A/B is the admission
    # evidence for DL4J_TPU_PALLAS_CONVBN — auto stays off until a
    # sustained win is recorded here, the lstm_helper_mode precedent).
    # fwd+bwd, like every other entry: training is the workload.
    convbn_shapes = ([(64, 56, 56, 64, jnp.bfloat16),
                      (32, 28, 28, 512, jnp.bfloat16),
                      (64, 56, 56, 64, jnp.float32)] if on_tpu
                     else [(2, 4, 4, 8, jnp.float32)])
    for (cb_, ch_, cw_, cc_, cdt_) in convbn_shapes:
        xb = jnp.asarray(
            rng.standard_normal((cb_, ch_, cw_, cc_)) * 0.5, cdt_)
        sc = jnp.asarray(rng.standard_normal(cc_) * 0.1 + 1.0, jnp.float32)
        sh = jnp.asarray(rng.standard_normal(cc_) * 0.1, jnp.float32)
        brc = pk.pick_bn_block(xb.shape, cdt_)
        cdt_name = "bf16" if cdt_ == jnp.bfloat16 else "f32"
        ctag = f"convbn_{cdt_name}_b{cb_}_hw{ch_}_c{cc_}"
        if not brc:
            out[ctag] = {"note": "no block plan fits — XLA path only"}
            continue

        def bn_step(fn):
            # scale/shift ride the carry so the bwd covers the full
            # epilogue vjp (dx AND dscale/dshift), matching training
            def loss(x, s, h):
                return (fn(x, s, h).astype(jnp.float32) ** 2).sum()

            def step(carry, i):
                import jax as _j
                x, s, h = carry
                dx, ds, dh = _j.grad(loss, argnums=(0, 1, 2))(x, s, h)
                return (x - (1e-4 * dx).astype(x.dtype),
                        s - 1e-4 * ds, h - 1e-4 * dh)
            return step

        def _ab_convbn(xb=xb, sc=sc, sh=sh, brc=brc, tag=ctag):
            tk = _ab_window(bn_step(
                lambda x, s, h: pk.bn_act(x, s, h, "relu", brc, interp)),
                (xb, sc, sh), iters)
            tx = _ab_window(bn_step(
                lambda x, s, h: pk.bn_act_reference(x, s, h, "relu")),
                (xb, sc, sh), iters)
            entry(tag, tk, tx)

        guarded(ctag, _ab_convbn)

    out["_note"] = (
        "long-window in-session A/B (bench._ab_window, >=100-iter "
        "windows); flash admission boundary measured AT t=1024 in both "
        "dtypes; LSTM long-t/small-b regime probed and unreachable by "
        "kernel design (see ops/pallas_kernels.lstm_helper_mode); "
        "xent = fused linear+softmax-xent kernel vs XLA materialized "
        "logits at the transformer vocab-head shape (targets ride the "
        "scan carry, not the closure, which would bake a 256 MB "
        "constant into the program); convbn = fused BatchNorm "
        "epilogue act(x*scale+shift) vs the XLA reference at ResNet "
        "hot-block shapes (admission evidence for "
        "DL4J_TPU_PALLAS_CONVBN); entries failing per-"
        "kernel record 'skipped: <reason>' instead of killing the sweep")
    return out


def bench_serving(on_tpu: bool) -> dict:
    """Sustained-QPS serving row (ROADMAP item 2's acceptance target):
    an offered-load sweep over the overload-hardened runtime
    (serving/runtime.py — buckets, deadlines, shedding, breaker).

    Method: measure closed-loop capacity with hammering clients, then
    drive OPEN-loop offered load at 0.5x / 1.0x / 2.0x of it and record
    what a production LB would see: accepted QPS, server-side p50/p99
    latency (queue wait + dispatch), shed rate, and median queue depth.
    The 2x point is the graceful-degradation number — accepted QPS must
    hold near capacity while the excess is shed with typed errors, not
    queued into unbounded latency. A fresh server per point keeps the
    latency/depth rings unpolluted; the jitted forward is shared so only
    the first warmup compiles."""
    import threading as _threading

    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.serving.buckets import BucketSpec
    from deeplearning4j_tpu.serving.errors import ServingError
    from deeplearning4j_tpu.serving.runtime import InferenceServer
    from deeplearning4j_tpu.util import jaxcompat

    feat = 64 if on_tpu else 16
    hidden = 512 if on_tpu else 32
    rng = np.random.default_rng(0)
    w1 = jnp.asarray(rng.standard_normal((feat, hidden)).astype(np.float32)
                     * 0.1)
    w2 = jnp.asarray(rng.standard_normal((hidden, 8)).astype(np.float32)
                     * 0.1)
    fwd = jaxcompat.jit(lambda x: jnp.tanh(x @ w1) @ w2,
                        watch_name="bench.serving")

    def dispatch(xp):
        return np.asarray(fwd(jnp.asarray(xp)))

    def fresh_server():
        # TWO buckets: enough to show the padding discipline, few enough
        # that warmup covers every executable and the retrace detector
        # stays silent (the serving steady-state contract)
        s = InferenceServer(dispatch=dispatch, batch_limit=32,
                            queue_limit=64, wait_ms=1.0,
                            buckets=BucketSpec(32, sizes=(8, 32)),
                            name="bench")
        s.warmup(np.zeros((1, feat), np.float32))
        return s

    # closed-loop capacity probe: enough hammering clients to keep the
    # coalescer's batches full (under-concurrency would underestimate
    # the batching path and make the sweep's "2x" point no overload)
    probe = fresh_server()
    n_clients, probe_s = 32, 0.6
    done = [0] * n_clients

    def hammer(k):
        x = np.zeros((1, feat), np.float32)
        end = time.perf_counter() + probe_s
        while time.perf_counter() < end:
            probe.output(x, deadline_s=2.0)
            done[k] += 1
    ts = [_threading.Thread(target=hammer, args=(k,), daemon=True)
          for k in range(n_clients)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(probe_s + 5.0)
    probe.shutdown()
    capacity = sum(done) / probe_s

    def point(mult: float) -> dict:
        server = fresh_server()
        target = max(capacity * mult, 1.0)
        dur, k_clients, deadline_s = 1.0, 16, 0.25
        period = k_clients / target
        lock = _threading.Lock()
        stats = {"shed": 0}
        pending = []

        def client(k):
            x = np.zeros((1, feat), np.float32)
            t_next = time.perf_counter() + period * (k / k_clients)
            end = time.perf_counter() + dur
            while t_next < end:
                pause = t_next - time.perf_counter()
                if pause > 0:
                    time.sleep(pause)
                try:
                    req = server.submit(x, deadline_s=deadline_s)
                    with lock:
                        pending.append(req)
                except ServingError:
                    with lock:
                        stats["shed"] += 1
                # no catch-up bursts: a paced client that fell behind
                # (sleep jitter) re-anchors instead of machine-gunning
                t_next = max(t_next + period,
                             time.perf_counter() - period)
        cts = [_threading.Thread(target=client, args=(k,), daemon=True)
               for k in range(k_clients)]
        for t in cts:
            t.start()
        for t in cts:
            t.join(dur + 5.0)
        ok = err = 0
        for req in pending:
            try:
                server.result(req)
                ok += 1
            except ServingError:
                err += 1
        snap = server.snapshot()
        server.shutdown()
        total = ok + err + stats["shed"]
        return {
            "offered_x": mult,
            "offered_qps_target": round(target, 1),
            # sleep() pacing undershoots at kHz rates: report what the
            # clients actually attempted, not the nominal target
            "offered_qps": round(total / dur, 1),
            "accepted_qps": round(ok / dur, 1),
            "latency_p50_ms": (round(snap["latency_p50_s"] * 1e3, 3)
                               if snap["latency_p50_s"] else None),
            "latency_p99_ms": (round(snap["latency_p99_s"] * 1e3, 3)
                               if snap["latency_p99_s"] else None),
            "shed_rate": round((err + stats["shed"]) / max(1, total), 4),
            "queue_depth_p50": snap["queue_depth_p50"],
        }

    sweep = [point(m) for m in (0.5, 1.0, 2.0)]
    overload = sweep[-1]

    # per-model fleet rows (serving/registry.py + serving/router.py):
    # two differently-sized models hosted side by side in ONE registry,
    # each hammered closed-loop through the Router so the number covers
    # the routed path — name dispatch, per-version metrics — not the
    # bare server. Gated per model by --check-regression via the
    # {model=...} row keys.
    from deeplearning4j_tpu.serving.registry import ModelRegistry
    from deeplearning4j_tpu.serving.router import Router

    fleet = ModelRegistry()
    for mname, h in (("mlp", hidden), ("wide", hidden * 2)):
        wa = jnp.asarray(
            rng.standard_normal((feat, h)).astype(np.float32) * 0.1)
        wb = jnp.asarray(
            rng.standard_normal((h, 8)).astype(np.float32) * 0.1)
        mfwd = jaxcompat.jit(lambda x, a=wa, b=wb: jnp.tanh(x @ a) @ b,
                             watch_name=f"bench.serving.{mname}")
        fleet.register(
            mname,
            dispatch=(lambda xp, f=mfwd: np.asarray(f(jnp.asarray(xp)))),
            batch_limit=32, queue_limit=64, wait_ms=1.0,
            buckets=BucketSpec(32, sizes=(8, 32)))
        fleet.warm(mname, example=np.zeros((1, feat), np.float32))
    router = Router(fleet)
    per_model = []
    for mname in ("mlp", "wide"):
        n_cl, span_s = 16, 0.4
        got = [0] * n_cl

        def mham(k, name=mname):
            x = np.zeros((1, feat), np.float32)
            end = time.perf_counter() + span_s
            while time.perf_counter() < end:
                router.output(name, x, deadline_s=2.0)
                got[k] += 1
        mts = [_threading.Thread(target=mham, args=(k,), daemon=True)
               for k in range(n_cl)]
        for t in mts:
            t.start()
        for t in mts:
            t.join(span_s + 5.0)
        per_model.append({
            "metric": "serving_sustained_qps",
            "model": mname,
            "value": round(sum(got) / span_s, 1),
            "unit": "requests/sec",
            "mode": "closed_loop_routed",
        })
    fleet.shutdown()

    return {
        "metric": "serving_sustained_qps",
        # headline: accepted QPS under 2x offered load — the graceful-
        # degradation number (shed the excess, keep serving)
        "value": overload["accepted_qps"],
        "unit": "requests/sec@2x_offered",
        "capacity_qps": round(capacity, 1),
        "deadline_s": 0.25,
        "shed_policy": "reject_newest",
        "sweep": sweep,
        "per_model": per_model,
        "mixed": False,
    }


def bench_serving_autoscale(on_tpu: bool) -> dict:
    """Elastic-fleet row (serving/autoscaler.py + serving/tenancy.py):
    step the offered load to 2x one replica's capacity and measure how
    long the pool takes to absorb it.

    Headline is time-to-stable: from the load step until the pool has
    scaled out AND the aggregate queue-depth p50 is back under the
    scale-out band. Sub-rows pin the two isolation guarantees:
    `serving_autoscale_cold_compiles` must stay 0 (replicas share the
    jitted forward and warm through the same buckets, so scale-out
    never compiles) and `serving_autoscale_tenant_p99_spread_ms` (two
    equal-weight tenants offered equal load must see near-equal p99 —
    the weighted-fair queue's fairness number)."""
    import threading as _threading

    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.serving.autoscaler import Autoscaler
    from deeplearning4j_tpu.serving.buckets import BucketSpec
    from deeplearning4j_tpu.serving.errors import ServingError
    from deeplearning4j_tpu.serving.runtime import InferenceServer
    from deeplearning4j_tpu.serving.tenancy import TenancyController
    from deeplearning4j_tpu.util import jaxcompat

    feat = 64 if on_tpu else 16
    hidden = 512 if on_tpu else 32
    rng = np.random.default_rng(0)
    w1 = jnp.asarray(rng.standard_normal((feat, hidden)).astype(np.float32)
                     * 0.1)
    w2 = jnp.asarray(rng.standard_normal((hidden, 8)).astype(np.float32)
                     * 0.1)
    fwd = jaxcompat.jit(lambda x: jnp.tanh(x @ w1) @ w2,
                        watch_name="bench.autoscale")

    def dispatch(xp):
        return np.asarray(fwd(jnp.asarray(xp)))

    tenancy = TenancyController(default_rate=1e6)
    for t in ("gold", "silver"):
        tenancy.add_tenant(t, rate=1e6, weight=1.0)

    def factory(name, tenancy_ctrl):
        s = InferenceServer(dispatch=dispatch, batch_limit=32,
                            queue_limit=64, wait_ms=1.0,
                            buckets=BucketSpec(32, sizes=(8, 32)),
                            tenancy=tenancy_ctrl, name=name)
        s.warmup(np.zeros((1, feat), np.float32))
        return s

    pool = Autoscaler(factory, min_replicas=1, max_replicas=3,
                      queue_depth_high=8.0, queue_depth_low=1.0,
                      ema_high_s=10.0, ema_low_s=0.0,
                      min_dwell_s=0.05, tenancy=tenancy,
                      name="bench-fleet")
    # the pin: every replica spawned during scale-out must hit the
    # shared jitted forward's cache, never the compiler
    raw_jit = getattr(fwd, "__wrapped_jit__", fwd)
    compiles_before = raw_jit._cache_size()

    # closed-loop capacity of the single boot replica
    n_probe, probe_s = 16, 0.4
    done = [0] * n_probe

    def hammer(k):
        x = np.zeros((1, feat), np.float32)
        end = time.perf_counter() + probe_s
        while time.perf_counter() < end:
            pool.output(x, deadline_s=2.0, tenant="gold")
            done[k] += 1
    ts = [_threading.Thread(target=hammer, args=(k,), daemon=True)
          for k in range(n_probe)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(probe_s + 5.0)
    capacity = sum(done) / probe_s

    # 2x load step, split over two equal-weight tenants; the main
    # thread IS the control loop (pull-driven evaluate ticks)
    dur, k_clients, deadline_s = 2.0, 24, 1.0
    target = max(capacity * 2.0, 8.0)
    period = k_clients / target
    stop = _threading.Event()
    shed = [0] * k_clients

    def client(k):
        x = np.zeros((1, feat), np.float32)
        tenant = "gold" if k % 2 == 0 else "silver"
        t_next = time.perf_counter() + period * (k / k_clients)
        while not stop.is_set():
            pause = t_next - time.perf_counter()
            if pause > 0:
                time.sleep(pause)
            try:
                pool.output(x, deadline_s=deadline_s, tenant=tenant)
            except ServingError:
                shed[k] += 1
            t_next = max(t_next + period, time.perf_counter() - period)
    cts = [_threading.Thread(target=client, args=(k,), daemon=True)
           for k in range(k_clients)]
    t0 = time.perf_counter()
    for t in cts:
        t.start()
    stable_at = None
    scaled = False
    end = t0 + dur
    while time.perf_counter() < end:
        pool.evaluate()
        snap = pool.snapshot()
        sig = snap["signals"]
        scaled = scaled or snap["replicas_live"] > 1
        if (scaled and stable_at is None
                and sig["queue_depth_p50"] < pool.queue_depth_high):
            stable_at = time.perf_counter() - t0
        time.sleep(0.01)
    stop.set()
    for t in cts:
        t.join(5.0)
    cold_compiles = raw_jit._cache_size() - compiles_before
    final = pool.snapshot()
    tsnap = tenancy.snapshot()["tenants"]
    p99s = [tsnap[t]["latency_p99_s"] for t in ("gold", "silver")
            if tsnap.get(t, {}).get("latency_p99_s") is not None]
    spread_ms = (round(abs(p99s[0] - p99s[1]) * 1e3, 3)
                 if len(p99s) == 2 else None)
    pool.shutdown()
    # an unstable run (never re-converged inside `dur`) reports the
    # full window — a regression, not a silently-missing row
    time_to_stable = round(stable_at if stable_at is not None else dur, 3)
    row = {
        "metric": "serving_autoscale_time_to_stable_s",
        "value": time_to_stable,
        "unit": "s@2x_load_step",
        "capacity_qps": round(capacity, 1),
        "replicas_final": final["replicas_live"],
        "scale_events": [(e["direction"], e["reason"])
                         for e in final["events"]],
        "shed_total": sum(shed),
        "per_model": [{
            "metric": "serving_autoscale_cold_compiles",
            "value": int(cold_compiles),
            "unit": "compiles@scale_out",
        }],
        "mixed": False,
    }
    if spread_ms is not None:
        row["per_model"].append({
            "metric": "serving_autoscale_tenant_p99_spread_ms",
            "value": spread_ms,
            "unit": "ms",
        })
    return row


def _introspection_fields(compiles_before: int,
                          total_spans_before: int = 0) -> dict:
    """compile_count + peak_hbm_bytes + input-pipeline columns for one
    config's emission dict (telemetry/introspect.py + health.py).
    peak_bytes_in_use is process-cumulative on PJRT, so per-config peaks
    are monotone across a sweep; None on backends without memory stats
    (CPU smoke runs). The input_bound verdict + etl p50 are scoped to
    the spans this config recorded (`total_spans_before` counts RECORDED
    spans, so the window survives ring-buffer eviction — prior configs'
    spans can never leak in; at worst this config's own earliest spans
    are truncated); configs that drive raw step loops (no etl/step
    spans) report "unknown". The prefetch queue-depth median is
    process-cumulative monitor state, so it is attached only when this
    config's own window produced a verdict."""
    try:
        from deeplearning4j_tpu.telemetry import health as thealth
        from deeplearning4j_tpu.telemetry import introspect
        from deeplearning4j_tpu.telemetry import trace as ttrace

        fields = {"compile_count": (introspect.watcher().compile_count()
                                    - compiles_before)}
        stats = introspect.hbm_stats()
        peaks = [int(ms.get("peak_bytes_in_use",
                            ms.get("bytes_in_use", 0)))
                 for ms in stats.values()]
        fields["peak_hbm_bytes"] = max(peaks) if peaks else None
        tr = ttrace.tracer()
        start = max(0, total_spans_before - tr.dropped)
        verdict = thealth.input_verdict(records=tr.records()[start:])
        fields["input_bound"] = verdict["verdict"]
        fields["etl_p50_ms"] = verdict["etl_p50_ms"]
        fields["prefetch_queue_depth_p50"] = (
            verdict["queue_depth_p50"]
            if verdict["verdict"] != "unknown" else None)
        # compiled-HLO collective census split by link class (zeros when
        # DL4J_TPU_COLLECTIVE_CENSUS is off — the census is opt-in
        # because it double-compiles every trace-cache miss)
        totals = introspect.watcher().collective_totals()
        dcn = sum(r.get("bytes_dcn", 0) for r in totals.values())
        fields["collective_bytes_ici"] = int(
            sum(r.get("bytes", 0) for r in totals.values()) - dcn)
        fields["collective_bytes_dcn"] = int(dcn)
        return fields
    except Exception:
        return {}


def run_metric(name: str, args, on_tpu: bool) -> dict:
    """Run one BASELINE.md config; returns the emission dict (plus the
    introspection columns: mfu where a cost model exists,
    peak_hbm_bytes, compile_count, input_bound verdict)."""
    try:
        from deeplearning4j_tpu.telemetry import introspect
        from deeplearning4j_tpu.telemetry import trace as ttrace

        tr = ttrace.tracer()
        compiles_before = introspect.watcher().compile_count()
        total_spans_before = len(tr) + tr.dropped  # running record total
    except Exception:
        compiles_before = 0
        total_spans_before = 0
    d = _run_metric_inner(name, args, on_tpu)
    d.update(_introspection_fields(compiles_before, total_spans_before))
    return d


def _run_metric_inner(name: str, args, on_tpu: bool) -> dict:
    mixed = not args.fp32
    if name == "resnet50":
        batch = args.batch or (128 if on_tpu else 2)
        iters = args.iters or (40 if on_tpu else 2)
        try:
            ips, mfu, wab = bench_resnet50(batch, iters, mixed=mixed)
        except Exception as e:  # OOM etc: fall back to smaller batch
            print(f"resnet50 bench failed ({type(e).__name__}: {e}); "
                  f"retrying batch=16", file=sys.stderr)
            ips, mfu, wab = bench_resnet50(16, iters, mixed=mixed)
        return {
            "metric": "resnet50_images_per_sec_per_chip",
            "value": round(ips, 2),
            "unit": "images/sec/chip",
            "vs_baseline": round(ips / BASELINE_PER_CHIP, 3),
            "mixed": mixed,
            "mfu": (mfu["mfu"] if mfu else None),
            "mfu_source": (mfu["source"] if mfu else None),
            "roofline_bound": (mfu["bound"] if mfu else None),
            # in-session four-knob A/B (training/engine.py window K,
            # prefetch, donation, convbn) + the dispatch tax the window
            # amortizes, machine-readable
            "window_ab": wab,
            "host_overhead_ms": (wab or {}).get("host_overhead_ms"),
        }
    if name == "lstm":
        cps = bench_lstm(args.batch or (64 if on_tpu else 4),
                         args.iters or (100 if on_tpu else 2))
        return {
            "metric": "graves_lstm_chars_per_sec",
            "value": round(cps, 2),
            "unit": "chars/sec",
            "vs_baseline": round(cps / PINNED["lstm"], 3),
            "mixed": False,
        }
    if name == "transformer":
        tps, wab = bench_transformer(args.batch or (16 if on_tpu else 2),
                                     args.iters or (30 if on_tpu else 2),
                                     seq_len=512 if on_tpu else 64,
                                     mixed=mixed)
        return {
            "metric": "transformer_lm_tokens_per_sec",
            "value": round(tps, 2),
            "unit": "tokens/sec",
            "vs_baseline": round(tps / PINNED["transformer"], 3),
            "mixed": mixed,
            "window_ab": wab,
            "host_overhead_ms": (wab or {}).get("host_overhead_ms"),
        }
    if name == "serving":
        return bench_serving(on_tpu)
    if name == "serving_autoscale":
        return bench_serving_autoscale(on_tpu)
    if name == "lenet":
        # sub-ms steps: need a long window or the 1x/3x difference is
        # noise-dominated (can even come out negative)
        ips = bench_lenet(args.batch or 256,
                          args.iters or (500 if on_tpu else 5))
        return {
            "metric": "lenet_images_per_sec",
            "value": round(ips, 2),
            "unit": "images/sec",
            "vs_baseline": round(ips / PINNED["lenet"], 3),
            "mixed": False,
        }
    # CPU smoke runs must downscale like every other config: 16384^3
    # chains would take hours off-TPU
    tf = bench_gemm() if on_tpu else bench_gemm(size=512, iters=3)
    try:
        from deeplearning4j_tpu.telemetry import profiler

        # the GEMM probe's FLOPs are exact, so its fraction-of-peak IS
        # its MFU (against the live platform's peak, not the pinned v5e
        # constant vs_baseline uses — identical on the TPU, honest on
        # CPU smoke runs)
        gemm_mfu = round(tf * 1e12 / profiler.peak_flops(dtype="bf16"), 4)
    except Exception:
        gemm_mfu = None
    return {
        "metric": "gemm_bf16_tflops_per_chip",
        "value": round(tf, 2),
        "unit": "TFLOPS",
        "vs_baseline": round(tf / V5E_BF16_PEAK_TFLOPS, 3),  # = MFU
        "mixed": True,
        "mfu": gemm_mfu,
        "mfu_source": "exact(2n^3)",
        "roofline_bound": "compute",
    }


def bench_smoke(args) -> dict:
    """Sub-minute CPU smoke of the full per-row machinery, exercised
    from tier-1 (tests/test_bench_smoke.py) so the bench harness itself
    cannot rot between hardware rounds: a tiny LeNet through the
    scan-timed marginal plus the four-knob in-session A/B
    (_window_ab_fields auto-drops K to 2 off-accelerator; the convbn
    arm self-skips on cpu). Emits the same row schema as the real
    benches so _bench_rows / --check-regression parse it unchanged."""
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.zoo import LeNet

    batch = args.batch or 8
    iters = args.iters or 3
    net = LeNet().init()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, 28, 28, 1),
                                        dtype=np.float32))
    y = jnp.asarray(np.eye(10, dtype=np.float32)[
        rng.integers(0, 10, batch)])
    dt = _timed_scan_steps(net, x, y, iters, tuple_args=False)
    # convbn=True so the cpu self-skip marker is exercised too
    wab = _session_ab_fields(net, x, y, iters, tuple_args=False,
                             scan_dt=dt, label="smoke", convbn=True)
    # the smoke doubles as the self-hosting lint gate: the source passes
    # (jaxlint JX*, concurrency DLC*) AND the shardlint selfcheck (the
    # zoo TransformerLM planned under fsdp=2 x tp=2, DLA015-DLA018) must
    # be clean, so a rule regression surfaces in tier-1
    # (tests/test_bench_smoke.py) even between hardware rounds
    from deeplearning4j_tpu.analysis import lint_all

    lint_rep = lint_all()
    # the smoke also runs the closed loop end to end (engine fit with
    # AUTOTUNE armed); a crash in it fails the smoke, its decision count
    # is reported only
    tuning = _tuning_smoke_fields()
    # and the federation frame path: frame-build p50 under budget, so a
    # scrape-path cost regression surfaces in tier-1 too
    try:
        federation = _federation_smoke_fields()
    except Exception as e:
        federation = {"ok": False, "error": f"{type(e).__name__}: {e}"}
    return {
        "metric": "smoke_lenet_images_per_sec",
        "value": round(batch * iters / dt, 2),
        "unit": "images/sec",
        "mixed": False,
        "window_ab": wab,
        "host_overhead_ms": (wab or {}).get("host_overhead_ms"),
        "lint": {"ok": not lint_rep.diagnostics,
                 "findings": len(lint_rep.diagnostics)},
        "tuning": tuning,
        "federation": federation,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="all",
                    choices=["resnet50", "lenet", "lstm", "transformer",
                             "gemm", "serving", "serving_autoscale",
                             "all"])
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--iters", type=int, default=0)
    ap.add_argument("--fp32", action="store_true",
                    help="disable bf16 mixed-precision activations")
    ap.add_argument("--check-regression", nargs=2, metavar=("OLD", "NEW"),
                    default=None,
                    help="compare two bench JSON artifacts (BENCH_r*.json "
                         "or BENCH_DETAIL.json) and exit 1 on a "
                         "regression past --threshold; runs without jax")
    ap.add_argument("--threshold", type=float, default=0.05,
                    help="relative regression tolerance "
                         "(default 0.05 = 5%%)")
    ap.add_argument("--smoke", action="store_true",
                    help="sub-minute CPU smoke of the row machinery "
                         "(tiny LeNet + the in-session A/B knobs, "
                         "window K auto-dropped); prints one JSON "
                         "line, writes no detail file")
    args = ap.parse_args()

    if args.check_regression:
        # pure JSON comparison — must work on machines with no
        # accelerator and must never pay (or fail on) backend init
        sys.exit(check_regression(*args.check_regression,
                                  threshold=args.threshold))

    import jax

    from deeplearning4j_tpu.util import compile_cache

    compile_cache.ensure()
    on_tpu = any(d.platform != "cpu" for d in jax.devices())

    if args.smoke:
        row = bench_smoke(args)
        print(json.dumps(row), flush=True)
        if not row["lint"]["ok"]:
            # the row already reports the count; the findings themselves
            # go to stderr so the stdout JSON contract stays one line
            print(f"smoke: self-hosting lint found "
                  f"{row['lint']['findings']} finding(s) — run "
                  f"`python -m deeplearning4j_tpu.cli lint`",
                  file=sys.stderr)
            sys.exit(1)
        if not row["federation"].get("ok"):
            print(f"smoke: telemetry frame-build budget failed — "
                  f"{row['federation']}", file=sys.stderr)
            sys.exit(1)
        return

    if args.model != "all":
        # telemetry forced on so the compile watcher's monitoring
        # listener counts this config's compilations too
        from deeplearning4j_tpu.telemetry import trace as ttrace_single

        ttrace_single.configure(enabled=True)
        try:
            print(json.dumps(run_metric(args.model, args, on_tpu)))
        finally:
            ttrace_single.configure(enabled=None)
        return

    # Telemetry rides along for the whole sweep (forced on, env-gate
    # independent): per-bench spans land in BENCH_DETAIL['telemetry'] so
    # BENCH_r* rounds carry a phase-level trajectory, not just end-to-end
    # numbers.
    from deeplearning4j_tpu.telemetry import metrics as tmetrics
    from deeplearning4j_tpu.telemetry import trace as ttrace

    tracer = ttrace.configure(enabled=True)
    tracer.clear()
    tmetrics.registry().reset()

    # Driver contract: the resnet line on stdout, flushed before the
    # (slower, best-effort) detail sweep so a truncated run still reports.
    with tracer.span("bench.resnet50", category="bench"):
        res = run_metric("resnet50", args, on_tpu)
    print(json.dumps(res), flush=True)

    detail = {
        "_note": ("vs_baseline is value/floor with floors from July "
                  "sessions on another machine — in-session A/Bs, not "
                  "cross-snapshot deltas, establish kernel wins"),
        "resnet50": res,
    }
    for name in ("gemm", "lenet", "lstm", "transformer", "serving",
                 "serving_autoscale"):
        try:
            with tracer.span(f"bench.{name}", category="bench"):
                detail[name] = run_metric(name, args, on_tpu)
        except Exception as e:
            detail[name] = {"metric": name, "error":
                            f"{type(e).__name__}: {e}"}
            print(f"{name} bench failed: {e}", file=sys.stderr)
    # closed-loop acceptance row (docs/TUNING.md): auto-tuned vs default
    # knobs on the same engine workload, in-session like every other A/B;
    # the ratio feeds --check-regression so a controller regression
    # (worse decisions round-over-round) gates like a perf regression
    try:
        with tracer.span("bench.auto_vs_default", category="bench"):
            detail["auto_vs_default"] = _auto_vs_default_fields()
    except Exception as e:
        detail["auto_vs_default"] = {"metric": "auto_vs_default_speedup",
                                     "error": f"{type(e).__name__}: {e}"}
        print(f"auto_vs_default ab failed: {e}", file=sys.stderr)
    # offline knob-grid search trace (tuning/sweep.py): what exhaustive
    # search found, recorded next to what the incremental rules chose
    try:
        with tracer.span("bench.tuning_sweep", category="bench"):
            from deeplearning4j_tpu.tuning.sweep import run_sweep

            detail["tuning"] = run_sweep(model="lenet", iters=16,
                                         batch=args.batch or 16)
    except Exception as e:
        detail["tuning"] = {"error": f"{type(e).__name__}: {e}"}
        print(f"tuning sweep failed: {e}", file=sys.stderr)
    try:
        with tracer.span("bench.kernel_ab", category="bench"):
            detail["ab"] = bench_kernel_ab(on_tpu)
    except Exception as e:
        # per-kernel failures are already recorded as "skipped" entries
        # inside bench_kernel_ab; this is the harness-level belt for
        # anything escaping that (never a traceback on stdout). The
        # skip lands under the SAME 'ab' key every round uses, so
        # round-over-round diff tooling sees an explicit marker rather
        # than the data silently vanishing.
        detail["ab"] = {"kernel_ab": f"skipped: {type(e).__name__}: {e}"}
        print(f"kernel ab skipped: {e}", file=sys.stderr)
    # phase medians + counter totals (telemetry/trace.py summary schema):
    # the machine-readable per-round perf trajectory future BENCH_r*
    # comparisons diff against
    from deeplearning4j_tpu.telemetry import health as thealth

    detail["telemetry"] = {
        "phases": tracer.summary(),
        "counters": tmetrics.registry().snapshot(),
        "input_pipeline": thealth.input_verdict(),
    }
    ttrace.configure(enabled=None)  # back to the env gate
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "BENCH_DETAIL.json")
    with open(out, "w") as f:
        json.dump(detail, f, indent=2)
    print(f"detail -> {out}", file=sys.stderr)
    # checked-in gate invocation: every full sweep self-compares against
    # the newest committed BENCH_r* round on stderr (advisory here — the
    # hard gate is the explicit `--check-regression OLD NEW` run between
    # rounds, which exits nonzero on a regression)
    import glob

    prior = sorted(glob.glob(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "BENCH_r[0-9][0-9].json")))
    if prior:
        print(f"regression gate vs {os.path.basename(prior[-1])}:",
              file=sys.stderr)
        check_regression(prior[-1], out, stream=sys.stderr)


if __name__ == "__main__":
    main()
