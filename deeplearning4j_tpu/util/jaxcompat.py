"""The jit seam every hot-path entry point binds through.

``jit`` is ``jax.jit`` plus the compile watcher (telemetry/introspect.py)
and the donation metadata the analyzer audits. ``REMAT_KEEP`` is the one
name the 'full' remat policy reads; it stands here because `ops/` and `nn/`
both tag with it and `ops/` imports nothing of `nn/`. The remat policies'
names and their lowering (``maybe_remat``) stand beside it for the same
reason: the models wrap a layer's call in them, and so does a container
LAYER around its nested layers' applications (`nn/` imports nothing of
`parallel/`, whose `layout.py` names them for the runtime packages).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax

#: `jax.ad_checkpoint.checkpoint_name` tag for a value the 'full' remat
#: policy keeps although it recomputes everything else: a value that costs
#: more to compute again than to keep. Four uses, one rule: what comes back
#: from a sub-computation that is ITSELF a checkpoint (`hybrid.over_row_groups`:
#: it reruns in its own backward, and would run a third time in the block's
#: recompute), the flash forward kernel's output and logsumexp
#: (`pallas_kernels._flash_vjp_fwd`: all its backward kernel needs beside
#: q, k, v, so the block's recompute does not call the forward kernel again),
#: the q, k, v a latent-attention layer hands the kernels
#: (`hybrid.LatentAttention`: two wide products, two rotations, a broadcast and
#: a concatenate to remake — a 769 ms step of five such layers fell by 29.6 ms —
#: for 0.57 GB a layer at [2, 32, 8192, 192 | 192 | 128] bfloat16; where q, k, v
#: are ONE product and a rotation away, as in the other attention layers,
#: they are recomputed), and what a routed-expert layer's backward reads that
#: is dear to remake and small to hold (`hybrid.RoutedExperts`): `h`, the first
#: grouped product's output — two thirds of a swiglu expert's forward work,
#: the narrow side of the block — where it is at most `hybrid.H_KEEP_BYTES`,
#: the sort's `order`, `inv` and group sizes (two argsorts to remake, under
#: 2 MB), the router's logits and, under the sigmoid recipe, the chosen ids (a
#: float32 HIGHEST product and a selection to remake; a few MB). The recompute still gathers the buffer, applies the
#: activation and runs `act(h) Wd`: outputs as large as a block's input. A
#: layer whose rows cross the interconnect to their experts
#: (`RoutedExperts.exchanged`) also keeps what ARRIVED, `xs` in the receiver's
#: order: as large as a block's input times `top_k`, but two passes over it and
#: an `all_to_all` to remake (Mellum 2: 503 MB a layer and chip against 58 ms
#: of a 723 ms step); what came BACK is no residual of anything
#: (`hybrid._rows_home`), so a block's recompute sends no row either way.
#: Outside a `jax.checkpoint` the tag lowers to nothing.
REMAT_KEEP = "dl4j_remat_keep"

#: stable policy-name order, weakest to strongest activation saving —
#: bench/test code iterates this to check watermark monotonicity
REMAT_POLICY_NAMES = ("none", "dots_saveable", "full", "offload")

_POLICY_CACHE: Dict[str, Any] = {}


def canonical_policy(name: Any) -> str:
    """Normalize a remat selector (None/bool/str) to a canonical name."""
    if name is None or name is False or name == "none":
        return "none"
    if name is True or name == "full":
        return "full"
    n = str(name)
    if n in REMAT_POLICY_NAMES:
        return n
    raise ValueError(
        f"unknown remat policy {name!r}; choose one of "
        f"{REMAT_POLICY_NAMES} (or a bool: True='full', False='none')")


def remat_policy(name: Any):
    """The jax.checkpoint `policy=` object for a canonical name ('full'
    saves nothing but what is tagged `REMAT_KEEP` — a value that costs
    more to compute again than to keep: the output of an inner checkpoint
    (`hybrid.over_row_groups`), so that it is not run a third time, the
    flash forward kernel's output and logsumexp (`pallas_kernels`), so that
    it is not run a second time, a latent-attention layer's q, k, v
    (`hybrid.LatentAttention`), so that its projections, rotations and
    concatenate are not, and a routed-expert layer's first grouped product
    within `hybrid.H_KEEP_BYTES`, its sort and its router's logits (sigmoid:
    the chosen ids too) and, where it exchanges rows between expert-parallel
    ranks, the rows that arrived (`hybrid.RoutedExperts`), so that the
    recompute sends none again). Cached so the same name always returns
    the SAME callable: a fresh policy closure per call would defeat the jit
    trace cache."""
    n = canonical_policy(name)
    if n in _POLICY_CACHE:
        return _POLICY_CACHE[n]
    cp = jax.checkpoint_policies
    if n == "dots_saveable":
        pol = cp.dots_saveable
    elif n == "offload":
        # dot outputs leave HBM for pinned host memory
        pol = cp.offload_dot_with_no_batch_dims("device", "pinned_host")
    elif n == "full":
        pol = cp.save_only_these_names(REMAT_KEEP)
    else:  # 'none'
        pol = None
    _POLICY_CACHE[n] = pol
    return pol


def maybe_remat(fn: Callable, name: Any) -> Callable:
    """Wrap `fn` in jax.checkpoint under the named policy; identity for
    'none'. The single seam parallel/transformer.py's stages, the
    config-DSL per-layer forward and a container layer's nested
    applications route through (the runtime packages name it as
    `parallel.layout.maybe_remat`)."""
    n = canonical_policy(name)
    if n == "none":
        return fn
    return jax.checkpoint(fn, policy=remat_policy(n))


def jit(fn, *, watch_name=None, **jit_kwargs):
    """``jax.jit`` through the compile-watcher seam (telemetry/
    introspect.py). The repo's hot-path jit entry points (train steps,
    output fns, ParallelWrapper's SPMD steps) bind here so the watcher
    can count compilations, time them, and flag retrace storms.

    Gate contract: with ``DL4J_TPU_TELEMETRY`` off the wrapper is the
    raw jitted call behind one enabled-check — no fingerprinting, no
    allocation (the PR 3 disabled-path policy). What is counted all the
    same, by the compile account's ``jax.monitoring`` listeners and not
    here: every trace, lowering and backend compile (or cache read) with
    its seconds and its function's name, and the persistent cache's hits
    and misses (``telemetry.fit_log()``'s ``compile``). What needs the
    gate is this seam's own: argument fingerprints, the retrace warning,
    the ``compile`` span, the collective census. ``.lower`` (and the raw
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
