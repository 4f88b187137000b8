"""The seams PR 21 put between the program and the machine it runs on: the
one owner of the JAX compile-cache directory (util/compile_cache.py) and
chip_smoke.py's refusal to run anywhere but on a TPU."""
import os
import subprocess
import sys

import jax

from deeplearning4j_tpu.serving import warmstart
from deeplearning4j_tpu.util import compile_cache

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv):
    """A fresh CPU-pinned interpreter with no cache directory placed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop(compile_cache.ENV_VAR, None)
    return subprocess.run([sys.executable, *argv], env=env, cwd=_ROOT,
                          capture_output=True, text=True, timeout=120)


class TestCompileCache:
    def test_placed_from_outside_is_never_touched(self, monkeypatch,
                                                  tmp_path):
        """JAX_COMPILATION_CACHE_DIR set: no code sets a directory — not
        ensure(), not warmstart.enable() (which used to repoint the cache
        at the warm-manifest dir)."""
        placed = str(tmp_path / "placed")
        monkeypatch.setenv(compile_cache.ENV_VAR, placed)
        before = jax.config.jax_compilation_cache_dir
        floor = jax.config.jax_persistent_cache_min_compile_time_secs
        try:
            assert compile_cache.ensure() == placed
            manifests = warmstart.enable(str(tmp_path / "manifests"))
            assert jax.config.jax_compilation_cache_dir == before
            assert manifests == str(tmp_path / "manifests")
            assert not os.path.exists(placed)  # nothing created it either
        finally:
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", floor)

    def test_unset_is_the_fixed_checkout_path(self, monkeypatch):
        """Unset: <checkout>/.jax_cache — a function of where the package
        lives, identical in this process and in a fresh one (no tempfile,
        pid or time in the path: the path is part of the cache key)."""
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        expected = os.path.join(_ROOT, ".jax_cache")
        assert compile_cache.ensure() == expected
        assert jax.config.jax_compilation_cache_dir == expected
        code = ("import jax; from deeplearning4j_tpu.util import "
                "compile_cache as c; print(c.ensure()); "
                "print(jax.config.jax_compilation_cache_dir)")
        proc = _run(["-c", code])
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert proc.stdout.split() == [expected, expected]


def test_chip_smoke_refuses_to_run_without_a_tpu():
    """JAX_PLATFORMS=cpu: non-zero exit with a "no TPU" message, no result
    line, and nothing built — the device check comes before any model."""
    proc = _run([os.path.join(_ROOT, "chip_smoke.py")])
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout
    assert "train:" not in proc.stdout and "ResNet" not in proc.stdout
