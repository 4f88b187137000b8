"""The integer-label cells' train steps compiled at their real size for a
described v5e (as test_compile_v5e.py does for the dense-label cells): the
hybrid step must fit one chip beside nothing (<= 15.75 GB: 15.32 GB with
what remat keeps since PRs 39 and 50, the delta rule's core mapped over rows
and the head + loss in row blocks), hold no float [N, V] array, admit the flash kernels at head
256, t 8192 (Mosaic refuses them there without the raised VMEM limit) and
run its experts through XLA's grouped product; the GPT-2 step must drop the
1.65 GB label operand, and over four chips' data mesh keep every device's
loss loop on its own rows."""
import re
from unittest import mock

import pytest

from benchmark.tests.test_compile_v5e import load, step_bytes, topo  # noqa: F401


def compile_ids_step(topo, cfg, traffic, chips=1):  # noqa: F811
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark import program
    from deeplearning4j_tpu import dtypes

    mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    repl = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("data", None))

    def like(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=repl), tree)

    try:
        net = program.build_net(cfg)
        shape = (traffic["per_chip_batch"] * chips, cfg["input"]["seq_len"])
        ids = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rows)
        args = (like(net.params), like(net.state), like(net.opt_state),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
                jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl),
                ids, ids, None, None)
        step = net._build_train_step()
        with mock.patch("jax.default_backend", return_value="tpu"), jax.set_mesh(mesh):
            return step.lower(*args).compile()
    finally:
        dtypes.set_mixed_precision(False)


def test_hybrid_step_fits_one_chip(topo):  # noqa: F811
    cfg = load("configs", "qwen3-next-80b-a3b-l4")
    compiled = compile_ids_step(topo, cfg, load("traffic", "train_ids_t8192_b2"))
    total = step_bytes(compiled)
    print(f"hybrid step: {total} bytes")
    assert 12e9 < total < 15.75e9, total              # 15 320 503 808 B (PR 51)
    text = compiled.as_text()
    # flash (PR 30: one backward kernel), the scalar delta rule's chunks (PR 37), the mixers'
    # short convolution + silu (PR 41); the attention layer's normed quarter-head rotation
    # stays XLA (PR 46)
    assert {"dl4j_flash_fwd", "dl4j_flash_bwd", "dl4j_gdn_fwd", "dl4j_gdn_bwd",
            "dl4j_convsilu_fwd", "dl4j_convsilu_bwd"} == set(
        re.findall(r"dl4j_[a-z]+_[a-z_]*?(?=_(?:bh|n)\d)", text))
    assert "bh32_t8192_d256" in text and "ragged-dot" in text
    assert not re.search(r"(f32|bf16)\[16384,18992\]", text)     # the head in row blocks
    # the delta core's rows are mapped and the state lives in the kernels' VMEM: what the
    # step holds of it is every chunk's start, a row at a time, as the forward kernel writes it
    assert re.search(r"f32\[128,1,32,128,128\]", text)
    assert not re.search(r"f32\[128,2,32,128,128\]", text)
    assert not re.search(r"(f32|bf16)\[[12],32,128,128\]", text)     # no scan carries it


def test_gpt2_ids_step_drops_the_label_operand(topo):  # noqa: F811
    cfg = load("configs", "gpt2-small")
    compiled = compile_ids_step(topo, cfg, load("traffic", "train_ids_t1024_b8"))
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes < 163.1e6 * 12 + 1e8       # no 1.65 GB of labels
    assert step_bytes(compiled) < 0.5 * 16e9
    text = compiled.as_text()
    assert "bh96_t1024_d64" in text
    assert not re.search(r"f32\[8,1024,50257\]", text)


def test_gpt2_ids_step_over_four_chips_gathers_no_rows(topo):  # noqa: F811
    """`ParallelWrapper` over data=4: the row-block loop of head + loss runs
    per batch shard (`mesh.per_batch_shard`), so no device is handed
    another's rows or logits; the weight gradients are all-reduced."""
    cfg = load("configs", "gpt2-small")
    compiled = compile_ids_step(topo, cfg, load("traffic", "train_ids_t1024_b8"), 4)
    text = compiled.as_text()
    # without it GSPMD gathers x as bf16[16,2048,768] (and the labels) onto
    # every device, and each computes the whole head
    assert "all-reduce" in text and "all-gather" not in text
    assert not re.search(r"(f32|bf16)\[(32768|8192),50257\]", text)   # row blocks of 2048
    assert step_bytes(compiled) < 0.5 * 16e9                          # bytes on each device
