"""Both train steps compiled at their real size for a described v5e:2x2
topology (on-chip-measurement section 2, third rehearsal): what the chip's
compiler would refuse, and the bytes a step needs, cost no chip time. This
is where the GPT-2 cell's batch of 8 was settled (7.9 GB of the 16).

One file, topology inside a module fixture: only the worker that is given
this file loads the TPU library.
"""
import json
import os
import re
from unittest import mock

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM = 16e9


def load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: skip, loudly
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def compile_step(topo, cfg, traffic, chips):
    """The program's own train step, lowered for `chips` described chips
    with the cell's shapes and the wrapper's shardings."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark import program
    from deeplearning4j_tpu import dtypes

    mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    repl = NamedSharding(mesh, P())

    def rows(shape, dtype):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P("data", *[None] * (len(shape) - 1))))

    def like(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=repl), tree)

    try:
        net = program.build_net(cfg)
        n = traffic["per_chip_batch"] * chips
        spec = cfg["input"]
        if spec["kind"] == "tokens":
            x = rows((n, spec["seq_len"]), jnp.int32)
            y = rows((n, spec["seq_len"], spec["vocab"]), jnp.float32)
        else:
            x = rows((n, *spec["shape"]), jnp.float32)
            y = rows((n, spec["classes"]), jnp.float32)
        graph = isinstance(net.opt_state, dict)
        if graph:
            x, y = (x,), (y,)
        args = (like(net.params), like(net.state), like(net.opt_state),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
                jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl),
                x, y, None, None)
        step = net._build_train_step()
        # the layers ask jax.default_backend() whether to admit their TPU
        # kernels; the compile is for a TPU, so they are told so
        with mock.patch("jax.default_backend", return_value="tpu"), jax.set_mesh(mesh):
            return step.lower(*args).compile()
    finally:
        dtypes.set_mixed_precision(False)


def step_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_gpt2_small_step_fits_one_chip_with_flash_and_without_xent(topo):
    cfg = load("configs", "gpt2-small")
    compiled = compile_step(topo, cfg, load("traffic", "train_t1024_b8"), 1)
    total = step_bytes(compiled)
    assert 0.25 * HBM < total < 0.75 * HBM, total      # 7.9 GB when settled
    kernels = set(re.findall(r"dl4j_[a-z]+_[a-z_]*?(?=_(?:bh|n)\d)", compiled.as_text()))
    # flash attention admits at t = 1024, head 64; the fused xent kernel
    # declines a vocabulary of 50257 = 29 x 1733 (no block divides it)
    assert {"dl4j_flash_fwd", "dl4j_flash_bwd"} == kernels      # ONE backward kernel since PR 30
    assert not any("xent" in k for k in kernels), kernels
    assert "bh96_t1024_d64" in compiled.as_text()


@pytest.mark.parametrize("traffic,chips", [("train_b128", 1), ("train_dp4_b128", 4)])
def test_resnet50_step_fits(topo, traffic, chips):
    cfg = load("configs", "resnet50")
    compiled = compile_step(topo, cfg, load("traffic", traffic), chips)
    assert step_bytes(compiled) < 0.9 * HBM            # bytes on each device
    text = compiled.as_text()
    assert "tpu_custom_call" not in text               # no Pallas kernel on this path
    if chips > 1:
        assert "all-reduce" in text and "all-gather" not in text
