"""Persisted warm starts — a restarted replica's warmup() is a disk read.

PR 8's ``InferenceServer.warmup`` makes steady state compile-free by
dispatching every bucketed shape once — but each fresh replica (restart,
autoscale-up) still pays the full cold-compile bill before serving its
first request. This module removes that bill with two pieces:

  compilation cache   ``enable(cache_dir)`` turns JAX's persistent
                      compilation cache on in the one directory
                      ``util/compile_cache.py`` owns (placed from
                      outside by ``JAX_COMPILATION_CACHE_DIR``, else
                      ``<checkout>/.jax_cache``) and drops
                      ``jax_persistent_cache_min_compile_time_secs`` to
                      0 so EVERY serving executable is persisted (the
                      default 1 s floor would skip exactly the small
                      bucketed forwards a CPU replica compiles fastest).
                      The cache key is the lowered computation's
                      fingerprint, which the bucketed dispatch makes a
                      function of ``(model version, bucket signature)``
                      — the per-model key the fleet needs, for free.
  warm manifests      ``record_warm`` writes one small JSON per
                      ``(model, version)`` under ``cache_dir``
                      recording the request signature and bucket sizes
                      that were warmed. A fresh replica that has never
                      seen a request calls ``warmup_example`` /
                      ``load_manifest`` to synthesize the warmup batch
                      from the manifest alone — boot order no longer
                      depends on traffic.

Zero-cold-start is ASSERTED, not assumed: jax fires a monitoring event
per backend compile even when the executable came from the cache, so the
compile watcher (telemetry/introspect.py) counts cache-retrieval events
separately and ``watcher().cold_compile_count()`` is the number a
restart test pins to zero (tests/test_serving_fleet.py).

Gate: ``DL4J_TPU_WARM_CACHE`` — the manifest directory; when set, the
ModelRegistry enables warm starts at construction. ``enable`` is
also directly callable for embedders. Pure manifest I/O goes through
``resilience/checkpoint.py``'s atomic writer (a torn manifest must not
brick a replica boot).
"""
from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from deeplearning4j_tpu.util import envflags

WARM_CACHE_GATE = "DL4J_TPU_WARM_CACHE"
MANIFEST_PREFIX = "warm_"

_SLUG_RE = re.compile(r"[^A-Za-z0-9_.-]+")


def cache_dir_from_env() -> Optional[str]:
    """The DL4J_TPU_WARM_CACHE directory, or None when unset."""
    d = envflags.value(WARM_CACHE_GATE)
    return d or None


def enable(cache_dir: str) -> str:
    """Turn warm starts on: manifests live under ``cache_dir``; the
    compiled executables live in the process's ONE compile-cache
    directory (util/compile_cache.py — this function never repoints it),
    persisted for EVERY compile (min-compile-time floor to 0 — the
    bucketed serving forwards are exactly the fast compiles the default
    1 s floor would silently skip). Idempotent; returns the manifest
    directory."""
    import jax

    from deeplearning4j_tpu.util import compile_cache

    d = os.path.abspath(cache_dir)
    os.makedirs(d, exist_ok=True)
    compile_cache.ensure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def _slug(name: str) -> str:
    return _SLUG_RE.sub("_", name)


def manifest_path(cache_dir: str, model: str, version: str) -> str:
    return os.path.join(
        cache_dir, f"{MANIFEST_PREFIX}{_slug(model)}__{_slug(version)}.json")


def record_warm(cache_dir: str, model: str, version: str,
                example, bucket_sizes: Sequence[int]) -> str:
    """Persist the warm recipe for one model version: the per-row
    request signature (shape minus the batch axis + dtype) and the
    bucket sizes whose executables now sit in the compilation cache.
    Atomic write — a replica booting mid-write reads the old manifest or
    none, never a torn one."""
    from deeplearning4j_tpu.resilience.checkpoint import atomic_write_json

    row = np.asarray(example)[:1]
    manifest: Dict[str, Any] = {
        "model": model,
        "version": version,
        "row_shape": [int(s) for s in row.shape[1:]],
        "dtype": str(row.dtype),
        "buckets": sorted(int(b) for b in bucket_sizes),
    }
    os.makedirs(cache_dir, exist_ok=True)
    path = manifest_path(cache_dir, model, version)
    atomic_write_json(path, manifest)
    return path


def load_manifest(cache_dir: str, model: str,
                  version: str) -> Optional[Dict[str, Any]]:
    """The recorded warm recipe, or None when this (model, version) was
    never warmed against this cache dir (first boot ever)."""
    path = manifest_path(cache_dir, model, version)
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def warmup_example(manifest: Dict[str, Any]) -> np.ndarray:
    """Synthesize a one-row warmup batch from a manifest. Zeros are
    shape/dtype-faithful, which is all the trace cache keys on — the
    values never reach a user."""
    shape = [1] + [int(s) for s in manifest.get("row_shape", [])]
    return np.zeros(shape, dtype=np.dtype(manifest.get("dtype", "float32")))


def list_manifests(cache_dir: str) -> List[Dict[str, Any]]:
    """Every warm manifest under ``cache_dir`` (the /models endpoint's
    "what can boot warm here" listing)."""
    out: List[Dict[str, Any]] = []
    try:
        names = sorted(os.listdir(cache_dir))
    except OSError:
        return out
    for name in names:
        if not (name.startswith(MANIFEST_PREFIX) and name.endswith(".json")):
            continue
        try:
            with open(os.path.join(cache_dir, name)) as f:
                out.append(json.load(f))
        except (OSError, ValueError):
            continue
    return out
