"""The jit seam every hot-path entry point binds through.

``jit`` is ``jax.jit`` plus the compile watcher (telemetry/introspect.py)
and the donation metadata the analyzer audits. ``REMAT_KEEP`` is the one
name the 'full' remat policy reads; it stands here because `ops/` and `nn/`
both tag with it and `ops/` imports nothing of `nn/`.
"""
from __future__ import annotations

import functools

import jax

#: `jax.ad_checkpoint.checkpoint_name` tag for a value the 'full' remat
#: policy keeps although it recomputes everything else: a value that costs
#: more to compute again than to keep. Two uses, one rule: what comes back
#: from a sub-computation that is ITSELF a checkpoint (`hybrid.over_row_groups`:
#: it reruns in its own backward, and would run a third time in the block's
#: recompute), and the flash forward kernel's output and logsumexp
#: (`pallas_kernels._flash_vjp_fwd`: all its backward kernel needs beside
#: q, k, v, so the block's recompute does not call the forward kernel again).
#: Outside a `jax.checkpoint` the tag lowers to nothing.
REMAT_KEEP = "dl4j_remat_keep"


def jit(fn, *, watch_name=None, **jit_kwargs):
    """``jax.jit`` through the compile-watcher seam (telemetry/
    introspect.py). The repo's hot-path jit entry points (train steps,
    output fns, ParallelWrapper's SPMD steps) bind here so the watcher
    can count compilations, time them, and flag retrace storms.

    Gate contract: with ``DL4J_TPU_TELEMETRY`` off the wrapper is the
    raw jitted call behind one enabled-check — no fingerprinting, no
    allocation (the PR 3 disabled-path policy). ``.lower`` (and the raw
    jitted fn as ``__wrapped_jit__``) pass through for cost analysis.
    """
    jitted = jax.jit(fn, **jit_kwargs)
    name = watch_name or getattr(fn, "__qualname__", repr(fn))

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        from deeplearning4j_tpu.telemetry import introspect

        w = introspect.watcher()
        if not w.enabled:
            return jitted(*args, **kwargs)
        return w.call(jitted, name, args, kwargs)

    wrapper.lower = jitted.lower
    wrapper.__wrapped_jit__ = jitted
    # donation metadata for the analyzer's DLA013 seam audit
    # (analysis/donation.py): which positional buffers this seam donates
    donate = jit_kwargs.get("donate_argnums", ())
    wrapper.__donate_argnums__ = (
        (donate,) if isinstance(donate, int) else tuple(donate))
    wrapper.__watch_name__ = name
    return wrapper
