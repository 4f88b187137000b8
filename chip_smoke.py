#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py

One process drives every local TPU chip through the entry points a user
calls, at the full width of the models the repo trains, with depth as
published and weights random from a seed:

  device   fail at once unless JAX's first device is a TPU.
  train    zoo ResNet-50 (1000 classes, 224x224x3, mixed bf16, batch 128
           per chip) through ParallelWrapper.fit -> training/engine.py
           TrainingRun -> the ComputationGraph train step.
  lm       zoo TransformerLM (V8192, t512, d512, 8 heads, 6 layers, mixed,
           batch 16 per chip), same path; the compiled step must hold the
           flash and xent Mosaic kernels at PER-DEVICE shapes.
  kernels  every Pallas family compiled by Mosaic and compared with its XLA
           reference at a tolerance fixed from the dtype.
  serve    serving.InferenceServer over the ResNet-50 just trained: two
           warmed buckets, 32 requests, no compile after warm-up.

Exit code 0 and, as the LAST stdout line,
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
only when every phase passed. Any failed phase — or no TPU, or a directory
without the package — exits non-zero and prints no result line. A failure
is reported with its traceback and the remaining independent phases still
run, so one chip call shows every failure; nothing is retried or replaced
by another path.

It starts no process that needs the chip (a process that has touched JAX
holds its chips), needs no network (all inputs come from SEED), and keeps
its compile cache where util/compile_cache.py places it:
JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import sys
import time
import traceback

SEED = 20260926
# The zoo ResNet-50 trains at Nesterov lr 0.1, a rate that needs warm-up:
# from a random start on one repeated random batch it sends the loss UP for
# the first steps (6.4 -> 115 -> 82 over ten steps in a 64x64 CPU run),
# which would make "the loss fell" say nothing about the program. The smoke
# lowers the one scalar; the compiled step is otherwise the zoo's.
RESNET_LR = 0.003


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The full-width sizes. A CPU debugging harness may pass smaller ones
    to the phase functions; `main` never does."""

    image: int = 224
    classes: int = 1000
    resnet_batch: int = 128       # per chip
    resnet_steps: int = 8
    vocab: int = 8192
    seq: int = 512
    d_model: int = 512
    heads: int = 8
    layers: int = 6
    lm_batch: int = 16            # per chip
    lm_steps: int = 6
    flash_t: tuple = (512, 2048)
    xent_n: int = 8192
    lstm_chunk_bt: tuple = (8, 1024)
    lstm_full_bt: tuple = (64, 64)
    lstm_n: int = 256
    kda_nrh: tuple = (128, 1, 32)  # chunks, rows, heads: kimilinear_train_t8192's row
    gdn_nrhh: tuple = (128, 1, 16, 32)  # chunks, rows, key and value heads: qwen3next_train_t8192's row
    ssd_nrhg: tuple = (64, 1, 64, 8)  # chunks, rows, heads, groups: nemotron3nano_train_t8192's row
    # the short convolution's operands [n, r, h, c, d] and whether a bias: Kimi-Linear's, Nemotron's two
    conv_shapes: tuple = (((128, 1, 96, 64, 128), False), ((64, 1, 64, 128, 64), True),
                          ((64, 1, 16, 128, 128), True))
    # rows, tokens, (heads a part, parts that turn), head width, features turned: Ouro's [q | k | v],
    # and a head of two lane tiles of which a quarter turns (Qwen3-Next's)
    rope_shapes: tuple = ((1, 8192, (16, 16, 16), (True, True, False), 128, 128),
                          (2, 8192, (16, 2), (True, False), 256, 64))
    bn_batch: int = 128
    bn_shapes: tuple = ((112, 112, 64), (28, 28, 128), (56, 56, 256),
                        (28, 28, 512), (14, 14, 1024), (7, 7, 2048))
    serve_buckets: tuple = (8, 32)
    serve_requests: int = 32


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


_T0 = time.perf_counter()


# --------------------------------------------------------------------------
# device
# --------------------------------------------------------------------------
def phase_device() -> dict:
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU — JAX's first device is "
            f"{d0.platform}:{d0.device_kind} ({len(devs)} device(s)); "
            f"this check runs on the chip only")
    import jaxlib

    from deeplearning4j_tpu import native
    from deeplearning4j_tpu.telemetry import profiler
    from deeplearning4j_tpu.util import compile_cache

    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    cache_dir = compile_cache.ensure()
    n_entries = compile_cache.entries(cache_dir)
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}
    log(f"device platform={d0.platform} device_kind={d0.device_kind!r} "
        f"count={len(devs)} local={jax.local_device_count()} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu}")
    log(f"device compile_cache={cache_dir} "
        f"placed_by={'env' if os.environ.get(compile_cache.ENV_VAR) else 'default'} "
        f"entries_at_start={n_entries} "
        f"({'empty' if n_entries == 0 else 'warm'}) "
        f"native_recordio={'built' if native.available() else 'none (pure-python fallback)'}")
    # the peaks table is keyed by device_kind; an unknown chip raises here
    log(f"device peaks[{d0.device_kind!r}] bf16="
        f"{profiler.peak_flops(dtype='bf16') / 1e12:.0f} TFLOP/s "
        f"hbm={profiler.peak_hbm_bytes_per_s() / 1e9:.0f} GB/s")
    return info


# --------------------------------------------------------------------------
# helpers shared by train / lm
# --------------------------------------------------------------------------
def _fit(net, ds, batch, steps):
    """ParallelWrapper.fit over one repeated seeded batch: `steps` epochs of
    a one-batch iterator. Returns (wrapper, per-step losses)."""
    import jax

    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.optimize.listeners import CollectScoresListener
    from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper

    n = jax.local_device_count()
    scores = CollectScoresListener()
    net.set_listeners(scores)
    pw = ParallelWrapper(net, mesh_spec=MeshSpec(data=n))
    t0 = time.perf_counter()
    pw.fit(ListDataSetIterator(ds, batch=batch), epochs=steps)
    log(f"  fit: {steps} steps in {time.perf_counter() - t0:.1f}s "
        f"(compile included)")
    return pw, [s for _, s in scores.scores]


def _check_training(name, net, pw, losses, steps, before, x, per_chip):
    import jax
    import numpy as np

    from deeplearning4j_tpu.parallel.wrapper import _put

    n = jax.local_device_count()
    log(f"  {name} losses: " + " ".join(f"{v:.4f}" for v in losses))
    assert len(losses) == steps, (len(losses), steps)
    assert all(np.isfinite(losses)), f"{name}: non-finite loss {losses}"
    assert losses[-1] < losses[0], (
        f"{name}: loss did not fall over {steps} steps: {losses}")
    leaves = (jax.tree_util.tree_leaves(net.params)
              + jax.tree_util.tree_leaves(net.opt_state))
    for leaf in leaves:
        devs = leaf.devices()
        assert len(devs) == n and all(_on_chip(d) for d in devs), (
            f"{name}: a param/opt leaf lives on {devs}")
    changed = [not np.array_equal(np.asarray(a), b)
               for a, b in zip(_probe_leaves(net), before)]
    assert all(changed), f"{name}: params unchanged after fit ({changed})"
    # the placement the wrapper's fit applies to every batch
    xs = _put(pw.mesh, x)
    shards = [s.data.shape[0] for s in xs.addressable_shards]
    assert shards == [per_chip] * n, f"{name}: batch shards {shards}"
    peaks = {str(d): _peak_bytes(d) for d in jax.local_devices()}
    assert all(v > 0 for v in peaks.values()), f"{name}: idle chip {peaks}"
    log(f"  {name} batch shards={shards} rows; {len(leaves)} param/opt "
        f"leaves on {n} tpu device(s); peak_bytes_in_use="
        + " ".join(f"{v / 2**30:.2f}GiB" for v in peaks.values()))


def _on_chip(device) -> bool:
    """Separate so a CPU debugging harness can stand in for it."""
    return device.platform == "tpu"


def _peak_bytes(device) -> int:
    """Separate for the same reason: the CPU reports no memory stats."""
    return device.memory_stats()["peak_bytes_in_use"]


def _probe_leaves(net):
    """First and last parameter leaves — enough to show the step wrote."""
    import jax

    leaves = jax.tree_util.tree_leaves(net.params)
    return [leaves[0], leaves[-1]]


# --------------------------------------------------------------------------
# train: ResNet-50
# --------------------------------------------------------------------------
def phase_train(sz: Sizes):
    import jax
    import numpy as np

    from deeplearning4j_tpu import dtypes
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models import ComputationGraph
    from deeplearning4j_tpu.zoo import ResNet50

    n = jax.local_device_count()
    dtypes.set_mixed_precision(True)
    batch = sz.resnet_batch * n
    log(f"train: zoo ResNet50 classes={sz.classes} input={sz.image}x"
        f"{sz.image}x3 mixed bf16, batch {sz.resnet_batch}/chip x {n} "
        f"chip(s), {sz.resnet_steps} steps, lr {RESNET_LR}")
    conf = ResNet50(num_classes=sz.classes,
                    input_shape=(sz.image, sz.image, 3)).conf()
    conf.defaults.updater.learning_rate = RESNET_LR
    net = ComputationGraph(conf).init()
    rng = np.random.default_rng(SEED)
    x = rng.standard_normal((batch, sz.image, sz.image, 3),
                            dtype=np.float32)
    y = np.zeros((batch, sz.classes), np.float32)
    y[np.arange(batch), rng.integers(0, sz.classes, batch)] = 1.0
    before = [np.asarray(a) for a in _probe_leaves(net)]
    pw, losses = _fit(net, DataSet(x, y), batch, sz.resnet_steps)
    _check_training("train", net, pw, losses, sz.resnet_steps, before, x,
                    sz.resnet_batch)
    return net, pw, x


# --------------------------------------------------------------------------
# lm: TransformerLM, the default path that admits Pallas kernels
# --------------------------------------------------------------------------
def phase_lm(sz: Sizes):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu import dtypes
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.parallel.wrapper import _put
    from deeplearning4j_tpu.zoo import TransformerLM

    n = jax.local_device_count()
    dtypes.set_mixed_precision(True)
    batch = sz.lm_batch * n
    log(f"lm: zoo TransformerLM V={sz.vocab} t={sz.seq} d={sz.d_model} "
        f"heads={sz.heads} layers={sz.layers} mixed bf16, batch "
        f"{sz.lm_batch}/chip x {n} chip(s), {sz.lm_steps} steps")
    net = TransformerLM(num_classes=sz.vocab, max_length=sz.seq,
                        d_model=sz.d_model, n_heads=sz.heads,
                        n_layers=sz.layers).init()
    rng = np.random.default_rng(SEED + 1)
    ids = rng.integers(0, sz.vocab, (batch, sz.seq))
    x = ids.astype(np.int32)
    y = np.zeros((batch, sz.seq, sz.vocab), np.float32)
    np.put_along_axis(y, np.roll(ids, -1, 1)[..., None], 1.0, axis=-1)
    before = [np.asarray(a) for a in _probe_leaves(net)]
    pw, losses = _fit(net, DataSet(x, y), batch, sz.lm_steps)
    _check_training("lm", net, pw, losses, sz.lm_steps, before, x,
                    sz.lm_batch)

    # What is IN the executable — read from the compiled step, not from
    # the admission gates. Same function, mesh context, shapes and
    # shardings as the fit's own call, so this compile is a cache read.
    # The arguments are made OUTSIDE the mesh scope, as the wrapper makes
    # them: eager results under an ambient mesh are placed on that mesh,
    # which is another input sharding and so another program.
    t0 = time.perf_counter()
    args = (net.params, net.state, net.opt_state, jnp.asarray(0),
            jax.random.PRNGKey(0), _put(pw.mesh, x), _put(pw.mesh, y),
            None, None)
    with jax.set_mesh(pw.mesh):
        text = net._train_step.lower(*args).compile().as_text()
    kernels = sorted({k.rstrip("_")
                      for k in re.findall(r"dl4j_[a-z]+_[a-z0-9_]+", text)})
    log(f"  lm compiled step read back in {time.perf_counter() - t0:.1f}s: "
        f"{text.count('tpu_custom_call')} tpu_custom_call site(s), "
        f"{text.count('all-gather')} all-gather, "
        f"{text.count('all-reduce')} all-reduce mention(s)")
    for k in kernels:
        log(f"    kernel {k}")
    # names carry the shapes the kernel was built for (ops/pallas_kernels.
    # kernel_name): bh / n here are ONE device's share. Called directly
    # under GSPMD the kernels do not lower on a TPU mesh at all ("Mosaic
    # kernels cannot be automatically partitioned"), and interpreted on the
    # CPU they are silently built for the gathered global batch.
    bh = sz.lm_batch * sz.heads
    rows = sz.lm_batch * sz.seq
    want = [f"dl4j_flash_fwd_bh{bh}_t{sz.seq}_",
            f"dl4j_flash_bwd_bh{bh}_t{sz.seq}_",
            f"dl4j_xent_fwd_n{rows}_d{sz.d_model}_v{sz.vocab}_",
            f"dl4j_xent_bwd_idx_n{rows}_d{sz.d_model}_v{sz.vocab}_"]
    missing = [w for w in want if not any(k.startswith(w) for k in kernels)]
    assert not missing, (
        f"lm: compiled step lacks per-device Mosaic kernels {missing}; "
        f"found {kernels}")
    if n > 1:
        assert "all-gather" not in text, (
            "lm: the data-parallel step all-gathers — GSPMD replicated "
            "an operand of a kernel instead of running it per shard")
        log(f"  lm under the {n}-chip data mesh: flash and xent run inside "
            f"shard_map on each chip's {sz.lm_batch}-row shard (kernel "
            f"names show per-device bh={bh}, n={rows}); no all-gather in "
            f"the step, gradients all-reduce")
    else:
        log(f"  lm on one chip: flash and xent custom calls in the step at "
            f"bh={bh}, n={rows}")


# --------------------------------------------------------------------------
# kernels: Mosaic vs the XLA references
# --------------------------------------------------------------------------
# Every array a case needs rides as a jit ARGUMENT: a closed-over array is
# baked into the program as a constant, and at these sizes (256 MB of
# labels) that alone cost a minute of compile per case.
#
# Tolerances are fixed from the dtype, as max|kernel - ref| / max(1,
# max|ref|), against a reference at "highest" matmul precision. At the
# default precision the MXU rounds f32 matmul operands to bf16 (2^-8 ~ 4e-3
# relative) in Mosaic and XLA alike, so a kernel fed f32 still carries that
# rounding through every product: 1e-2. bf16 storage adds the rounding of P,
# dS and the outputs themselves: 3e-2.
TOL = {"float32": 1e-2, "bfloat16": 3e-2}


def _err(got, ref):
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    assert np.all(np.isfinite(got)), "non-finite kernel output"
    return float(np.max(np.abs(got - ref)) / max(1.0, np.max(np.abs(ref))))


def _compare(label, dtype, got, ref, failures):
    """got/ref: matching tuples of arrays (outputs then gradients)."""
    import jax.numpy as jnp

    tol = TOL[jnp.dtype(dtype).name]
    errs = [_err(g, r) for g, r in zip(got, ref)]
    ok = all(e <= tol for e in errs)
    log(f"  {'ok  ' if ok else 'FAIL'} {label}: max err "
        + " ".join(f"{e:.2e}" for e in errs) + f" (tol {tol:.0e})")
    if not ok:
        failures.append(f"{label}: err {errs} > tol {tol}")


def _f32(*arrs):
    import jax.numpy as jnp

    return tuple(a.astype(jnp.float32) for a in arrs)


def phase_kernels(sz: Sizes):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nn import activations as act_mod
    from deeplearning4j_tpu.nn import losses as loss_mod
    from deeplearning4j_tpu.ops import attention as att
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    from deeplearning4j_tpu.ops import xent_kernel as xk

    interpret = jax.default_backend() != "tpu"  # False past phase_device
    rng = np.random.default_rng(SEED + 2)
    failures: list = []
    highest = jax.default_matmul_precision("highest")

    def rnd(shape, dtype, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32)
                           * scale, dtype)

    def run(label, fn):
        """One family member; a refusal (Mosaic or otherwise) is a failure
        with its message, and the other families still report."""
        try:
            fn()
        except Exception as e:
            log(f"  FAIL {label}: {type(e).__name__}: "
                f"{str(e)[:1500]}")
            failures.append(f"{label}: {type(e).__name__}")

    # ---- flash attention fwd+bwd
    def flash_case(t, dtype, window=None):
        b, h, d = 4, 8, 64
        bq, bk = pk.pick_flash_blocks(t, d, dtype)
        q, k, v, g = (rnd((b, h, t, d), dtype, 0.5) for _ in range(4))

        def kern(q, k, v):
            return pk.flash_attention(q, k, v, True, None, bq, bk,
                                      interpret, window)

        def ref(q, k, v):
            with highest:
                return att.sdpa(q, k, v, causal=True, window=window)

        def both(f):
            def run(q, k, v, g):
                out, vjp = jax.vjp(f, q, k, v)
                return (out,) + tuple(vjp(g.astype(out.dtype)))
            return jax.jit(run)

        got = both(kern)(q, k, v, g)
        want = both(ref)(*_f32(q, k, v, g))
        _compare(f"flash t={t} d={d} {jnp.dtype(dtype).name} blocks="
                 f"({bq},{bk}) window={window} out,dq,dk,dv", dtype, got, want, failures)

    for t in sz.flash_t:
        for dtype in (jnp.bfloat16, jnp.float32):
            run(f"flash t={t} {jnp.dtype(dtype).name}",
                lambda t=t, dtype=dtype: flash_case(t, dtype))
    # the band's second bound, a head a program (static bounds) and a block a program (traced)
    for t, window in ((sz.flash_t[0], 192), (sz.flash_t[-1], 512)):
        run(f"flash t={t} window={window} bfloat16",
            lambda t=t, window=window: flash_case(t, jnp.bfloat16, window))

    # ---- fused linear + softmax-xent fwd+bwd
    softmax = act_mod.get("softmax")

    def xent_case(dtype, soft):
        n, d, v = sz.xent_n, sz.d_model, sz.vocab
        plan = xk.plan(n, d, v, dtype)
        assert plan is not None, f"xent plan refuses n={n} d={d} v={v}"
        x = rnd((n, d), dtype, 1.0)
        w = rnd((d, v), dtype, d ** -0.5)
        b = rnd((v,), jnp.float32, 0.1)
        labels = np.zeros((n, v), np.float32)
        labels[np.arange(n), rng.integers(0, v, n)] = 1.0
        if soft:  # label smoothing: the dense-label backward
            labels = labels * 0.9 + 0.1 / v
        labels = jnp.asarray(labels)
        g = rnd((n,), jnp.float32, 1.0)

        def kern(x, w, b, labels):
            return xk.linear_xent_rows(x, w, b, labels, plan, interpret)

        def ref(x, w, b, labels):
            with highest:
                z = jnp.dot(x, w, preferred_element_type=jnp.float32) + b
                return loss_mod.compute("mcxent", labels, z, softmax)[1]

        def both(f):
            def run(x, w, b, labels, g):
                out, vjp = jax.vjp(lambda x, w, b: f(x, w, b, labels),
                                   x, w, b)
                return (out,) + tuple(vjp(g))
            return jax.jit(run)

        got = both(kern)(x, w, b, labels, g)
        want = both(ref)(*_f32(x, w), b, labels, g)
        _compare(f"xent n={n} d={d} v={v} {jnp.dtype(dtype).name} "
                 f"{'soft' if soft else 'one-hot'} plan={plan} "
                 f"rows,dx,dw,db", dtype, got, want, failures)

    for dtype in (jnp.bfloat16, jnp.float32):
        for soft in (False, True):
            run(f"xent {jnp.dtype(dtype).name} soft={soft}",
                lambda dtype=dtype, soft=soft: xent_case(dtype, soft))

    # ---- LSTM scans fwd+bwd (f32)
    def lstm_case(b, t, chunked, peephole_mask):
        n = sz.lstm_n
        dtype = jnp.float32
        zx = rnd((b, t, 4 * n), dtype, 0.5)
        R = rnd((n, 4 * n), dtype, n ** -0.5)
        h0, c0 = rnd((b, n), dtype, 0.5), rnd((b, n), dtype, 0.5)
        p = rnd((3, n), dtype, 0.3) if peephole_mask else None
        mask = None
        if peephole_mask:  # ragged lengths, at least half of each row live
            lens = rng.integers(t // 2, t + 1, b)
            mask = jnp.asarray(
                (np.arange(t)[None, :] < lens[:, None]).astype(np.float32))
        gs = (rnd((b, t, n), dtype), rnd((b, n), dtype), rnd((b, n), dtype))
        if chunked:
            plan = pk.pick_lstm_chunk(zx.shape, dtype,
                                      masked=mask is not None)
            assert plan is not None, f"no chunk plan for {zx.shape}"
            bb, tc = plan
            tag = f"chunked bb={bb} tc={tc}"

            def kern(zx, R, h0, c0, p, mask):
                if p is None:
                    return pk.lstm_scan_chunked(zx, R, h0, c0, bb, tc,
                                                interpret, mask)
                return pk.lstm_scan_chunked_peephole(
                    zx, R, p, h0, c0, bb, tc, interpret, mask)
        else:
            bb = pk.pick_lstm_block(zx.shape, dtype)
            assert bb, f"no full-resident block for {zx.shape}"
            tag = f"full-resident bb={bb}"

            def kern(zx, R, h0, c0, p, mask):
                if p is None:
                    return pk.lstm_scan(zx, R, h0, c0, bb, interpret, mask)
                return pk.lstm_scan_peephole(zx, R, p, h0, c0, bb,
                                             interpret, mask)

        def ref(zx, R, h0, c0, p, mask):
            with highest:
                return pk._lstm_ref(zx, R, h0, c0, p, mask)

        def both(f):
            def run(zx, R, h0, c0, p, mask, gs):
                out, vjp = jax.vjp(
                    lambda zx, R, h0, c0, p: f(zx, R, h0, c0, p, mask),
                    zx, R, h0, c0, p)
                return tuple(out) + tuple(
                    x for x in vjp(gs) if x is not None)
            return jax.jit(run)

        args = (zx, R, h0, c0, p, mask, gs)
        got = both(kern)(*args)
        want = both(ref)(*args)
        _compare(f"lstm {tag} b={b} t={t} n={n} f32 "
                 f"{'peephole+mask' if peephole_mask else 'plain'} "
                 f"hs,hT,cT,dzx,dR,dh0,dc0[,dp]", dtype, got, want,
                 failures)

    cb, ct = sz.lstm_chunk_bt
    fb, ft = sz.lstm_full_bt
    for ph in (False, True):
        run(f"lstm chunked ph+mask={ph}",
            lambda ph=ph: lstm_case(cb, ct, True, ph))
    for ph in (False, True):
        run(f"lstm full-resident ph+mask={ph} (opt-in family)",
            lambda ph=ph: lstm_case(fb, ft, False, ph))

    # ---- bn_act epilogue fwd+bwd at the ResNet-50 stage shapes (opt-in)
    def bn_case(hwc):
        dtype = jnp.bfloat16
        shape = (sz.bn_batch,) + tuple(hwc)
        br = pk.pick_bn_block(shape, dtype)
        assert br, f"no bn_act block for {shape}"
        x, g = rnd(shape, dtype), rnd(shape, dtype)
        scale = rnd((hwc[-1],), jnp.float32, 0.5) + 1.0
        shift = rnd((hwc[-1],), jnp.float32, 0.5)

        def both(f):
            def run(x, scale, shift, g):
                out, vjp = jax.vjp(f, x, scale, shift)
                return (out,) + tuple(vjp(g))
            return jax.jit(run)

        got = both(lambda x, s, h: pk.bn_act(x, s, h, "relu", br,
                                             interpret))(x, scale, shift, g)
        want = both(lambda x, s, h: pk.bn_act_reference(x, s, h, "relu"))(
            x, scale, shift, g)
        _compare(f"bn_act {shape} bf16 rows/block={br} y,dx,dscale,dshift",
                 dtype, got, want, failures)

    for hwc in sz.bn_shapes:
        run(f"bn_act {hwc} (opt-in family)", lambda hwc=hwc: bn_case(hwc))

    # ---- the per-channel delta rule's chunk kernels fwd+bwd, through their door
    def kda_case():
        from deeplearning4j_tpu.nn.layers import hybrid
        from deeplearning4j_tpu.ops import delta

        (n, r, h), c, d = sz.kda_nrh, 64, 128
        unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
        q = unit(rnd((n, r, h, c, d), jnp.float32)) * d ** -0.5
        k, v = unit(rnd((n, r, h, c, d), jnp.float32)), rnd((n, r, h, c, d), jnp.float32)
        # log decays down to -1.6 a token: the fastest channels fall by e^-100 in a chunk
        g = -jnp.exp(jnp.asarray(rng.uniform(np.log(1e-3), np.log(1.6), (n, r, h, c, d)), jnp.float32))
        beta = jnp.asarray(rng.uniform(0.0, 1.0, (n, r, h, c)), jnp.float32)
        ct = rnd((n, r, h, c, d), jnp.float32)
        assert delta.kda_impl("auto", q, v) == "pallas" or interpret

        def both(f):
            def run(*a):
                (o, states), vjp = jax.vjp(f, *a)
                return (o, states) + tuple(vjp((ct, jnp.zeros_like(states))))
            return jax.jit(run)

        def ref(*a):
            with highest:
                return hybrid.chunk_channel_gated_delta_rule(*a)

        got = both(lambda *a: delta.kda_chunks(*a, impl="pallas"))(q, k, v, g, beta)
        want = both(ref)(q, k, v, g, beta)
        _compare(f"kda chunks n={n} r={r} h={h} c={c} d={d} float32 "
                 f"o,states,dq,dk,dv,dg,dbeta", jnp.float32, got, want, failures)

    run("kda chunks", kda_case)

    # ---- the scalar delta rule's chunk kernels fwd+bwd, through their door
    def gdn_case():
        from deeplearning4j_tpu.nn.layers import hybrid
        from deeplearning4j_tpu.ops import delta

        (n, r, hk, hv), c, d = sz.gdn_nrhh, 64, 128
        unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
        q = unit(rnd((n, r, hk, c, d), jnp.float32)) * d ** -0.5
        k, v = unit(rnd((n, r, hk, c, d), jnp.float32)), rnd((n, r, hv, c, d), jnp.float32)
        # per-token decays from 0.9999 down to 0.2: the fastest heads fall by e^-100 in a chunk
        g = -jnp.exp(jnp.asarray(rng.uniform(np.log(1e-4), np.log(1.6), (n, r, hv, c)), jnp.float32))
        beta = jnp.asarray(rng.uniform(0.0, 1.0, (n, r, hv, c)), jnp.float32)
        ct = rnd((n, r, hv, c, d), jnp.float32)
        assert delta.gdn_impl("auto", q, v) == "pallas" or interpret

        def both(f):
            def run(*a):
                o, vjp = jax.vjp(f, *a)
                return (o,) + tuple(vjp(ct))
            return jax.jit(run)

        def ref(*a):
            with highest:
                return hybrid.chunk_gated_delta_rule(*a)

        got = both(lambda *a: delta.gdn_chunks(*a, impl="pallas"))(q, k, v, g, beta)
        want = both(ref)(q, k, v, g, beta)
        _compare(f"gdn chunks n={n} r={r} hk={hk} hv={hv} c={c} d={d} float32 "
                 f"o,dq,dk,dv,dg,dbeta", jnp.float32, got, want, failures)

    run("gdn chunks", gdn_case)

    # ---- the state-space rule's chunk kernels fwd+bwd, through their door
    def ssd_case():
        from deeplearning4j_tpu.nn.layers import ssm
        from deeplearning4j_tpu.ops import delta

        (n, r, h, g), c, p, s = sz.ssd_nrhg, 128, 64, 128
        x, ct = rnd((n, r, h, c, p), jnp.float32), rnd((n, r, h, c, p), jnp.float32)
        b, cm = rnd((n, r, g, c, s), jnp.float32, 0.3), rnd((n, r, g, c, s), jnp.float32, 0.3)
        # per-token decays from 0.9999 down to 0.2: the fastest heads fall by e^-200 in a chunk
        a = -jnp.asarray(rng.uniform(1.0, 16.0, (h,)), jnp.float32)
        dt = jnp.exp(jnp.asarray(rng.uniform(np.log(1e-4), np.log(1.6), (n, r, h, c)), jnp.float32)) / -a[:, None]
        assert delta.ssd_impl("auto", x, b) == "pallas" or interpret

        def both(f):
            def run(*q):
                (y, states), vjp = jax.vjp(f, *q)
                return (y, states) + tuple(vjp((ct, jnp.zeros_like(states))))
            return jax.jit(run)

        def ref(*q):
            with highest:
                return ssm.ssd_chunked(*q)

        got = both(lambda *q: delta.ssd_chunks(*q, impl="pallas"))(x, dt, a, b, cm)
        want = both(ref)(x, dt, a, b, cm)
        _compare(f"ssd chunks n={n} r={r} h={h} g={g} c={c} p={p} s={s} float32 "
                 f"y,states,dx,ddt,da,db,dc", jnp.float32, got, want, failures)

    run("ssd chunks", ssd_case)

    # ---- the recurrent mixers' short convolution + silu fwd+bwd, through its door:
    # tokens on the sublanes (d 128) and on the lanes (d 64, with a bias)
    def conv_case(shape, bias):
        from deeplearning4j_tpu.nn.layers import hybrid
        from deeplearning4j_tpu.ops import delta

        h, d = shape[2], shape[4]
        x, ct = rnd(shape, jnp.bfloat16), rnd(shape, jnp.float32)
        w = rnd((4, h, 1, d), jnp.float32, 0.5)
        b = rnd((h, 1, d), jnp.float32, 0.5) if bias else None
        assert delta.conv_silu_impl("auto", x, w) == "pallas" or interpret

        def both(f):
            def run(*a):
                y, vjp = jax.vjp(f, *a)
                return (y,) + tuple(vjp(ct))
            return jax.jit(run)

        args = (x, w) + ((b,) if bias else ())
        got = both(lambda *a: delta.conv_silu_chunks(*a, impl="pallas"))(*args)
        want = both(hybrid._conv_silu if bias else lambda x_, w_: hybrid._conv_silu(x_, w_, None))(*args)
        # dx is bfloat16 on both sides: a unit in its last place apart where the float32 sums differ
        _compare(f"conv silu {shape} bias={bias} y,dw" + (",db" if bias else ""), jnp.float32,
                 got[:1] + got[2:], want[:1] + want[2:], failures)
        _compare(f"conv silu {shape} bias={bias} dx", jnp.bfloat16, got[1:2], want[1:2], failures)

    for shape, bias in sz.conv_shapes:
        run(f"conv silu {shape}", lambda shape=shape, bias=bias: conv_case(shape, bias))

    # ---- the rotation + head split of an attention projection's columns fwd+bwd, through its door
    def rope_case(b, t, heads, turned, d, rot):
        from deeplearning4j_tpu.nn.layers import hybrid
        from deeplearning4j_tpu.ops import attention as att

        a = rnd((b, t, sum(heads) * d), jnp.bfloat16)
        cts = tuple(rnd((b, n, t, d), jnp.bfloat16) for n in heads)
        assert att.rope_impl("auto", b, t, d, rot, a.dtype) == "pallas" or interpret

        def xla(a_):
            cols = jnp.split(a_, list(np.cumsum([n * d for n in heads])[:-1]), axis=-1)
            split = [c.reshape(b, t, n, d).transpose(0, 2, 1, 3) for c, n in zip(cols, heads)]
            return tuple(hybrid.rotary(x, rot, 1e6) if turn else x for x, turn in zip(split, turned))

        def both(f):
            def run(a_):
                out, vjp = jax.vjp(f, a_)
                return tuple(out) + tuple(vjp(cts))
            return jax.jit(run)

        got = both(lambda a_: att.rope_heads(a_, heads, turned, d, rot, 1e6, impl="pallas"))(a)
        _compare(f"rope heads b={b} t={t} heads={heads} d={d} rot={rot} bf16 parts,da",
                 jnp.bfloat16, got, both(xla)(a), failures)

    for shape in sz.rope_shapes:
        run(f"rope heads {shape}", lambda shape=shape: rope_case(*shape))

    assert not failures, "kernels: " + "; ".join(failures)


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------
def phase_serve(sz: Sizes, net, pw, x):
    import numpy as np

    from deeplearning4j_tpu.serving import InferenceServer
    from deeplearning4j_tpu.serving.buckets import BucketSpec
    from deeplearning4j_tpu.telemetry import introspect
    from deeplearning4j_tpu.telemetry import trace as trace_mod

    n = pw.mesh.shape["data"]
    buckets = BucketSpec(max(sz.serve_buckets), align=n,
                         sizes=sz.serve_buckets)
    log(f"serve: InferenceServer over the trained ResNet-50, buckets "
        f"{buckets.sizes} aligned to data={n}, {sz.serve_requests} requests")
    trace_mod.configure(enabled=True)  # the compile watcher counts only on
    server = None
    try:
        watcher = introspect.watcher()
        t0 = time.perf_counter()
        server = InferenceServer(model=net, mesh=pw.mesh,
                                 batch_limit=max(sz.serve_buckets),
                                 queue_limit=4 * sz.serve_requests,
                                 buckets=buckets, warmup_example=x[:1])
        log(f"  warmed {len(buckets.sizes)} buckets in "
            f"{time.perf_counter() - t0:.1f}s")
        compiles0 = watcher.compile_count()
        sizes = [1 + i % 4 for i in range(sz.serve_requests)]
        offs = np.concatenate([[0], np.cumsum(sizes)])
        rows = x[:offs[-1]]
        pending = [server.submit(rows[offs[i]:offs[i + 1]])
                   for i in range(sz.serve_requests)]
        outs = [server.result(p) for p in pending]
        compiled = watcher.compile_count() - compiles0
        assert compiled == 0, (
            f"serve: {compiled} compilation(s) after warm-up")
        got = np.concatenate(outs, axis=0)
        assert got.shape == (offs[-1], sz.classes), got.shape
        assert np.all(np.isfinite(got)), "serve: non-finite outputs"
        dispatched = sorted(b for _, b in server.dispatched_rows)
        assert server.dispatched_rows <= server.warmed_rows, (
            f"serve: dispatched a shape that was never warmed: "
            f"{server.dispatched_rows - server.warmed_rows}")
    finally:
        if server is not None:
            server.shutdown()
        trace_mod.configure(enabled=None)
    ref = np.asarray(net.output(rows))
    err = _err(got, ref)
    # softmax rows in (0, 1]; the server's bucketed batches and the direct
    # call are different executables over bf16 activations
    assert err <= TOL["bfloat16"], (
        f"serve: outputs differ from net.output by {err}")
    log(f"  {sz.serve_requests} requests ({offs[-1]} rows) answered, "
        f"0 compilations after warm-up, max |server - net.output| = "
        f"{err:.2e}; dispatched buckets {dispatched}")


# --------------------------------------------------------------------------
def main() -> int:
    info = phase_device()  # SystemExit (non-zero, no result line) off-chip
    sz = Sizes()
    failed = []
    trained = None

    def run(name, fn):
        t0 = time.perf_counter()
        try:
            out = fn()
        except Exception:
            log(f"{name}: FAILED after {time.perf_counter() - t0:.1f}s\n"
                + traceback.format_exc())
            failed.append(name)
            return None
        log(f"{name}: passed in {time.perf_counter() - t0:.1f}s")
        return out

    trained = run("train", lambda: phase_train(sz))
    run("lm", lambda: phase_lm(sz))
    run("kernels", lambda: phase_kernels(sz))
    if trained is None:
        log("serve: FAILED (needs the model the train phase did not "
            "produce)")
        failed.append("serve")
    else:
        run("serve", lambda: phase_serve(sz, *trained))
    log(f"total {time.perf_counter() - _T0:.1f}s")
    if failed:
        log(f"FAILED phases: {failed}")
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
