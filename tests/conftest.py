"""Test harness config.

Tests run on the CPU over an 8-device virtual mesh (SURVEY.md §4 'TPU-build
mapping'): XLA_FLAGS=--xla_force_host_platform_device_count=8 plays the
`local[N]` role the reference's Spark tests use. Nothing here reaches an
accelerator; the chip is exercised by `python chip_smoke.py` alone.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from deeplearning4j_tpu.datasets.dataset import DataSet  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(12345)


@pytest.fixture
def iris_like(rng):
    """Synthetic 3-class separable dataset shaped like IRIS (150x4)."""
    n, f, c = 150, 4, 3
    centers = rng.normal(0, 3.0, (c, f))
    ids = rng.integers(0, c, n)
    x = centers[ids] + rng.normal(0, 0.5, (n, f))
    y = np.zeros((n, c), np.float32)
    y[np.arange(n), ids] = 1.0
    return DataSet(x.astype(np.float32), y)
