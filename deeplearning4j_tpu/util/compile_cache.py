"""The one owner of JAX's persistent compilation-cache directory.

Every entry point that compiles (``TrainingRun``, ``ModelRegistry``,
``cli``, ``bench.py``, ``chip_smoke.py``) calls ``ensure()`` and nothing
else in the tree sets ``jax_compilation_cache_dir``.

  ``JAX_COMPILATION_CACHE_DIR`` set    JAX reads the variable itself at
      import; this module sets NO directory — whoever launched the
      process placed the cache (a machine that keeps it across runs gets
      warm starts for free).
  unset                                 ``<checkout>/.jax_cache`` (listed
      in .gitignore). The path is part of the cache key's environment: a
      tempfile, pid or timestamp directory would never hit, so it is a
      fixed function of where the package lives.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str:
    """``<checkout>/.jax_cache`` — beside the package, identical in every
    process started from the same checkout."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def ensure() -> str:
    """Resolve the compile-cache directory (see module docstring) and
    return it. Idempotent and cheap: after the first call it is one
    environment read and one config comparison."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    d = default_dir()
    if jax.config.jax_compilation_cache_dir != d:
        jax.config.update("jax_compilation_cache_dir", d)
    return d


def entries(d: str) -> int:
    """Number of files under the cache directory (0 when it does not
    exist yet) — what "was the cache warm at start" reports."""
    try:
        return sum(1 for n in os.listdir(d) if not n.startswith("."))
    except OSError:
        return 0
