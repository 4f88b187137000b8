"""Cost/MFU engine — achieved-vs-peak FLOPs and roofline classification.

"As fast as the hardware allows" (ROADMAP) is unverifiable without a
number for *allows*. This module produces that number two ways:

  exact     ``jax.jit(step).lower(...).cost_analysis()`` over the fitted
            train step — XLA's own FLOP and bytes-accessed count for the
            program actually executed;
  fallback  the PR 1 analyzer's DLA008 estimates
            (``analysis.estimate_costs``) when lowering is impossible
            (imported nets mid-restructure, exotic configs) — a crude
            dense-equivalent count, labeled as such in every report.

Dividing by a measured step time (the telemetry step-span median) gives
**MFU** (model FLOPs utilization, TPP's efficiency accounting,
arXiv:2104.05755) published as the ``dl4j_tpu_mfu`` gauge, and the
arithmetic intensity (FLOPs / HBM byte) against the platform ridge point
classifies the step **compute-bound vs memory-bound** (the roofline
model). Peaks come from a table keyed by the accelerator's
``device_kind`` (an unknown accelerator raises), overridable by
``DL4J_TPU_PEAK_FLOPS`` / ``DL4J_TPU_HBM_GBPS`` — measured-machine
numbers always beat the table.

Consumed by the ``profile`` CLI subcommand, the ``/profile`` endpoint
(ui/server.py) and bench.py's BENCH_DETAIL columns. docs/PROFILING.md
explains how to read the outputs.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

from deeplearning4j_tpu.telemetry import introspect
from deeplearning4j_tpu.telemetry import metrics as metrics_mod
from deeplearning4j_tpu.telemetry import trace as trace_mod
from deeplearning4j_tpu.util import envflags

PEAK_FLOPS_GATE = "DL4J_TPU_PEAK_FLOPS"
HBM_GBPS_GATE = "DL4J_TPU_HBM_GBPS"

# Accelerator rows are keyed by `device_kind` exactly as the chip
# reports it; an accelerator that is not in the table raises rather than
# borrow another chip's peaks. "TPU v5 lite" is the v5e (Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM; f32 taken as
# half the bf16 rate). The "cpu" rows are order-of-magnitude
# placeholders — MFU on CPU is only ever an "estimated" figure for smoke
# runs; override with the env gates for a measured machine.
_PEAK_FLOPS = {
    "TPU v5 lite": {"bf16": 197e12, "f32": 98.5e12},
    "cpu": {"bf16": 2e11, "f32": 2e11},
}
_HBM_BYTES_PER_S = {"TPU v5 lite": 819e9, "cpu": 5e10}


def platform() -> str:
    import jax

    return jax.devices()[0].platform


def _peaks_key(plat: Optional[str]) -> str:
    """Row of the peaks tables for this process's device: "cpu", or the
    accelerator's `device_kind`."""
    if (plat or platform()) == "cpu":
        return "cpu"
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in _PEAK_FLOPS:
        raise KeyError(
            f"no peak FLOP/s or HBM bandwidth on record for accelerator "
            f"{kind!r}: add its published peaks to telemetry/profiler.py "
            f"(known: {sorted(_PEAK_FLOPS)})")
    return kind


def peak_flops(plat: Optional[str] = None, dtype: str = "bf16") -> float:
    override = envflags.float_value(PEAK_FLOPS_GATE, 0.0)
    if override > 0:
        return override
    row = _PEAK_FLOPS[_peaks_key(plat)]
    return row.get(dtype, row["f32"])


def peak_hbm_bytes_per_s(plat: Optional[str] = None) -> float:
    override = envflags.float_value(HBM_GBPS_GATE, 0.0)
    if override > 0:
        return override * 1e9
    return _HBM_BYTES_PER_S[_peaks_key(plat)]


# ---------------------------------------------------------------------------
# cost extraction
# ---------------------------------------------------------------------------


def _normalize_cost(ca) -> Optional[Dict[str, float]]:
    """cost_analysis() returns a dict, a list of per-computation dicts,
    or None depending on jax/backend version — normalize to
    {'flops': f, 'bytes': b} or None."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = float(ca.get("flops", 0.0) or 0.0)
    byts = float(ca.get("bytes accessed", 0.0) or 0.0)
    if flops <= 0:
        return None
    return {"flops": flops, "bytes": byts}


def jit_cost(jitted, *args, **kwargs) -> Optional[Dict[str, float]]:
    """XLA cost analysis of a jitted callable at the given (concrete or
    ShapeDtypeStruct) arguments; None when the backend can't say.
    Accepts both raw jax.jit results and the jaxcompat.jit wrapper."""
    try:
        lower = getattr(jitted, "lower", None)
        if lower is None:
            return None
        lowered = lower(*args, **kwargs)
        # pre-compile analysis ONLY: a .compile() fallback would trigger
        # a second full backend compile of the step (minutes on big
        # nets) just to read a number the analyzer can estimate for free
        return _normalize_cost(lowered.cost_analysis())
    except Exception:
        return None


def train_step_cost(net, x, y) -> Optional[Dict[str, float]]:
    """Cost of the fitted train step for a MultiLayerNetwork or
    ComputationGraph at batch (x, y). Builds the step if needed."""
    try:
        import jax
        import jax.numpy as jnp

        if net._train_step is None:
            net._train_step = net._build_train_step()
        from deeplearning4j_tpu.models.computation_graph import (
            ComputationGraph,
        )

        x = jnp.asarray(x)
        y = jnp.asarray(y)
        it_ = jnp.asarray(0)
        rng = jax.random.PRNGKey(0)
        if isinstance(net, ComputationGraph):
            args = (net.params, net.state, net.opt_state, it_, rng,
                    (x,), (y,), None, None)
        else:
            args = (net.params, net.state, net.opt_state, it_, rng,
                    x, y, None, None)
        return jit_cost(net._train_step, *args)
    except Exception:
        return None


def analyzer_cost(conf, batch: int) -> Optional[Dict[str, float]]:
    """DLA008 fallback: dense-equivalent FLOPs (6·params·batch — fwd
    2PB + bwd 4PB, ignores conv weight reuse and attention, labeled
    'analyzer' wherever surfaced) and the estimated training working set
    as the bytes proxy."""
    try:
        from deeplearning4j_tpu.analysis import estimate_costs

        est = estimate_costs(conf, batch=batch)
        if not est:
            return None
        return {"flops": float(est["flops_per_step"]),
                "bytes": float(est["train_bytes"])}
    except Exception:
        return None


# ---------------------------------------------------------------------------
# MFU / roofline
# ---------------------------------------------------------------------------


def mfu_report(flops: float, byts: float, step_seconds: float,
               plat: Optional[str] = None, dtype: str = "bf16",
               source: str = "cost_analysis") -> Dict[str, Any]:
    """MFU + roofline classification for one step; publishes the
    dl4j_tpu_mfu / dl4j_tpu_arithmetic_intensity gauges."""
    plat = plat or platform()
    peak = peak_flops(plat, dtype)
    bw = peak_hbm_bytes_per_s(plat)
    achieved = flops / step_seconds if step_seconds > 0 else 0.0
    mfu = achieved / peak if peak > 0 else 0.0
    ai = flops / byts if byts > 0 else float("inf")
    ridge = peak / bw
    bound = "compute" if ai >= ridge else "memory"
    metrics_mod.gauge(
        "dl4j_tpu_mfu",
        "model FLOPs utilization of the last profiled step").set(mfu)
    if byts > 0:
        metrics_mod.gauge(
            "dl4j_tpu_arithmetic_intensity",
            "FLOPs per HBM byte of the last profiled step").set(ai)
    return {
        "mfu": round(mfu, 4),
        "achieved_tflops": round(achieved / 1e12, 4),
        "peak_tflops": round(peak / 1e12, 2),
        "flops_per_step": flops,
        "bytes_per_step": byts,
        "arithmetic_intensity": (round(ai, 3)
                                 if ai != float("inf") else None),
        "ridge_flops_per_byte": round(ridge, 3),
        "bound": bound,
        "platform": plat,
        "source": source,
    }


def step_mfu(net, x, y, step_seconds: float,
             dtype: str = "bf16") -> Optional[Dict[str, Any]]:
    """Best-available MFU for a model's step: XLA cost analysis first,
    analyzer estimate as the labeled fallback."""
    cost = train_step_cost(net, x, y)
    source = "cost_analysis"
    if cost is None:
        batch = int(getattr(x, "shape", (32,))[0])
        cost = analyzer_cost(net.conf, batch)
        source = "analyzer(DLA008)"
    if cost is None or step_seconds <= 0:
        return None
    return mfu_report(cost["flops"], cost["bytes"], step_seconds,
                      dtype=dtype, source=source)


# ---------------------------------------------------------------------------
# the `profile` CLI engine
# ---------------------------------------------------------------------------

_ZOO = ("lenet", "resnet50", "lstm", "transformer")


def _build_model(name: str, batch: int):
    """(net, x, y, dtype) for a zoo name or a model-zip path, with
    synthetic data shaped like bench.py's generators."""
    import numpy as np

    rng = np.random.default_rng(0)

    def one_hot(ids, n):
        ids = np.asarray(ids)
        out = np.zeros(ids.shape + (n,), np.float32)
        np.put_along_axis(out, ids[..., None], 1.0, axis=-1)
        return out

    if name == "lenet":
        from deeplearning4j_tpu.zoo import LeNet

        net = LeNet().init()
        x = rng.standard_normal((batch, 28, 28, 1)).astype(np.float32)
        y = one_hot(rng.integers(0, 10, batch), 10)
        return net, x, y, "f32"
    if name == "resnet50":
        from deeplearning4j_tpu.zoo import ResNet50

        net = ResNet50(num_classes=1000, input_shape=(224, 224, 3)).init()
        x = rng.standard_normal((batch, 224, 224, 3)).astype(np.float32)
        y = one_hot(rng.integers(0, 1000, batch), 1000)
        return net, x, y, "f32"
    if name == "lstm":
        from deeplearning4j_tpu.zoo import TextGenerationLSTM

        zm = TextGenerationLSTM(max_length=32)
        net = zm.init()
        ids = rng.integers(0, zm.num_classes, (batch, 32))
        x = one_hot(ids, zm.num_classes)
        y = one_hot(np.roll(ids, -1, axis=1), zm.num_classes)
        return net, x, y, "f32"
    if name == "transformer":
        from deeplearning4j_tpu.zoo import TransformerLM

        zm = TransformerLM(num_classes=2048, max_length=64, d_model=128,
                           n_heads=4, n_layers=2)
        net = zm.init()
        ids = rng.integers(0, 2048, (batch, 64))
        x = ids.astype(np.int32)
        y = one_hot(np.roll(ids, -1, 1), 2048)
        return net, x, y, "f32"

    # anything else: a serialized model zip, data from its input type
    from deeplearning4j_tpu.models import restore_model

    net = restore_model(name)
    in_t = net._input_types[0] if hasattr(net, "_input_types") else None
    if in_t is None:
        raise ValueError(
            f"cannot synthesize data for {name!r}; use a zoo name "
            f"({', '.join(_ZOO)}) or a sequential model zip")
    shape = tuple(32 if d == -1 else d for d in in_t.shape(batch))
    x = rng.standard_normal(shape).astype(np.float32)
    out_t = net._input_types[-1]
    yshape = tuple(shape[1] if d == -1 else d for d in out_t.shape(batch))
    y = np.zeros(yshape, np.float32)
    idx = rng.integers(0, yshape[-1], yshape[:-1])
    np.put_along_axis(y, idx[..., None], 1.0, axis=-1)
    return net, x, y, "f32"


def profile_model(model: str = "lenet", iters: int = 20, batch: int = 16,
                  layer_every: int = 5) -> Dict[str, Any]:
    """Run `iters` training iterations on synthetic data with telemetry
    forced on and return the introspection report: step p50, MFU +
    roofline, peak HBM (or "unavailable"), compile count, top-k layers.
    The engine behind `python -m deeplearning4j_tpu.cli profile`."""
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator

    net, x, y, dtype = _build_model(model, batch)
    reps = (iters,) + (1,) * (x.ndim - 1)
    ds = DataSet(np.tile(x, reps), np.tile(y, reps))

    tracer = trace_mod.configure(enabled=True)
    try:
        introspect.configure(layer_every=layer_every)
        # the profile run pays the census's double compile on purpose:
        # the collectives table is half the point of profiling a mesh
        introspect.configure_census(True)
        introspect.reset()
        n_before = len(tracer)
        compiles_before = introspect.watcher().compile_count()
        t0 = time.perf_counter()
        net.fit(ListDataSetIterator(ds, batch=batch), epochs=1)
        wall_s = time.perf_counter() - t0
        # knob values ACTIVE during the profiled window, with provenance
        # (env vs tuner override) — the raw environment lies once the
        # tuner has applied a live override, so snapshot the effective
        # overlay here, before any later tick can move a knob again
        knobs = envflags.snapshot()

        from deeplearning4j_tpu.telemetry import health as health_mod

        summary = tracer.summary()
        step = summary.get("step", {})
        step_p50_s = step.get("p50_ms", 0.0) / 1e3
        mfu = step_mfu(net, x, y, step_p50_s, dtype=dtype)
        input_pipeline = health_mod.input_verdict()
        hbm_snap = metrics_mod.registry().snapshot()
        peak_hbm = hbm_snap.get("dl4j_tpu_hbm_peak_bytes")
        return {
            "model": model,
            "iters": iters,
            "batch": batch,
            "platform": platform(),
            "wall_seconds": round(wall_s, 3),
            "step_p50_ms": step.get("p50_ms"),
            "step_mean_ms": step.get("mean_ms"),
            "step_count": step.get("count"),
            "etl_p50_ms": summary.get("etl", {}).get("p50_ms"),
            "input_pipeline": input_pipeline,
            "mfu": mfu,
            "compile_count": (introspect.watcher().compile_count()
                              - compiles_before),
            "compile": introspect.watcher().snapshot(),
            "hbm": (introspect.sample_hbm() or "unavailable"),
            "peak_hbm_bytes": peak_hbm,
            "predicted_hbm_bytes": introspect.predicted_train_bytes(net),
            "top_layers": introspect.top_layers(),
            "collectives": introspect.watcher().collective_totals(),
            "spans_recorded": len(tracer) - n_before,
            "knobs": knobs,
        }
    finally:
        # a raising fit must not leave telemetry globally forced on (or
        # layer sampling armed, or the census's double compile) for the
        # rest of the process
        introspect.configure(layer_every=None)
        introspect.configure_census(None)
        trace_mod.configure(enabled=None)  # back to the env gate


def format_report(rep: Dict[str, Any]) -> str:
    """Human rendering of a profile_model report (the CLI's output)."""
    lines = [
        f"profile: {rep['model']}  (iters={rep['iters']}, "
        f"batch={rep['batch']}, platform={rep['platform']})",
        "-" * 64,
        f"step p50        {_ms(rep['step_p50_ms'])}   "
        f"(mean {_ms(rep['step_mean_ms'])}, n={rep['step_count']})",
        f"etl p50         {_ms(rep['etl_p50_ms'])}",
        f"compile count   {rep['compile_count']}",
    ]
    ip = rep.get("input_pipeline") or {}
    if ip.get("verdict") and ip["verdict"] != "unknown":
        depth = ip.get("queue_depth_p50")
        lines.append(
            f"input pipeline  {ip['verdict']}"
            + (f"  (prefetch queue depth p50 {depth})"
               if depth is not None else ""))
    mfu = rep.get("mfu")
    if mfu:
        lines.append(
            f"estimated MFU   {mfu['mfu'] * 100:.2f}%  "
            f"({mfu['achieved_tflops']} / {mfu['peak_tflops']} TFLOPS, "
            f"{mfu['bound']}-bound, source={mfu['source']})")
    else:
        lines.append("estimated MFU   unavailable (no cost model)")
    hbm = rep.get("hbm")
    if hbm == "unavailable" or not hbm:
        lines.append("HBM             unavailable (backend reports no "
                     "memory stats)")
    else:
        peak = rep.get("peak_hbm_bytes")
        pred = rep.get("predicted_hbm_bytes")
        lines.append(f"HBM peak        {_bytes(peak)}"
                     + (f"  (analyzer predicted {_bytes(pred)})"
                        if pred else ""))
    retraced = rep.get("compile", {}).get("retraced_fns") or []
    if retraced:
        lines.append(f"retrace warning {', '.join(retraced)}")
    col = rep.get("collectives") or {}
    if col:
        lines.append("collectives (compiled-HLO census, per-device "
                     "result bytes):")
        for kind in sorted(col):
            rec = col[kind]
            lines.append(
                f"  {kind:<18} x{rec.get('count', 0):<4} "
                f"{_bytes(rec.get('bytes', 0)):>12}  "
                f"(dcn {_bytes(rec.get('bytes_dcn', 0))}, "
                f"param-plane {_bytes(rec.get('bytes_param', 0))})")
    knobs = rep.get("knobs") or {}
    if knobs:
        lines.append("knobs active during window (non-default):")
        for name in sorted(knobs):
            rec = knobs[name]
            lines.append(f"  {name:<28} {rec['value']:<8} "
                         f"[{rec['provenance']}]")
    top = rep.get("top_layers") or []
    if top:
        lines.append("top layers (sampled fwd+bwd, total ms):")
        for row in top:
            lines.append(f"  {row['name']:<16} {row['layer']:<22} "
                         f"fwd {row['fwd_ms']:>8.2f}  "
                         f"bwd {row['bwd_ms']:>8.2f}")
    return "\n".join(lines)


def _ms(v) -> str:
    return "-" if v is None else f"{v:.2f} ms"


def _bytes(v) -> str:
    if v is None:
        return "-"
    v = float(v)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if v < 1024 or unit == "GiB":
            return f"{v:.2f} {unit}"
        v /= 1024
    return f"{v:.2f} GiB"
