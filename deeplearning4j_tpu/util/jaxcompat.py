"""The jit seam every hot-path entry point binds through.

``jit`` is ``jax.jit`` plus the compile watcher (telemetry/introspect.py)
and the donation metadata the analyzer audits.
"""
from __future__ import annotations

import functools

import jax


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
