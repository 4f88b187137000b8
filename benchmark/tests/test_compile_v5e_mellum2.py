"""The `mellum2_train_t8192_ep4` train step compiled at its real size for a
described 2 x 2 v5e: 1.78 B parameters at 16 B are 28.5 GB, so no chip holds
the stage; spread as `ParallelWrapper(MeshSpec(data=4))` spreads it — every
expert matrix split 4 ways on its expert dimension, everything else whole on
every chip — a device's step must stay under 15.4 GB by `memory_analysis()`
with 9.52 GB of it weights, gradients and moments. The HLO must hold an
`all-to-all` for each exchange, an `all-reduce` for the gradients of what is
whole everywhere and NO `all-gather` of an expert matrix; the banded flash
pair at a window of 1024 for the three sliding layers and the plain pair for
the full one, 32 heads each, and the grouped product over 4 x 24576 rows.

Nothing of the full size is made here: the net's arrays are shapes
(`jax.eval_shape` around the program's own `build_net`). One file, topology
inside a module fixture: only the worker that is given this file loads the TPU
library."""
import re
from unittest import mock

import pytest

from benchmark.tests.test_compile_v5e import load, step_bytes, topo  # noqa: F401


def compile_mesh_step(topo, cfg, traffic, chips=4):  # noqa: F811
    """The program's own train step lowered for `chips` described chips with
    the wrapper's placement: parameters and moments as their layers declare
    them, the rows over `data`."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import program
    from deeplearning4j_tpu import dtypes
    from deeplearning4j_tpu.parallel import MeshSpec, layout, mesh as mesh_mod

    mesh = mesh_mod.build_mesh(MeshSpec(data=chips), topo.devices[:chips])
    repl = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("data", None))
    made = []

    def shapes():
        made.append(program.build_net(cfg))
        return made[0].params, made[0].state, made[0].opt_state

    try:
        params, state, opt = jax.eval_shape(shapes)
        net = made[0]
        net.params, net.state, net.opt_state = params, state, opt
        placed = mesh_mod.model_param_shardings(mesh, net)
        specs = layout.specs_beyond(placed)
        assert specs is not None        # the expert matrices are declared split
        net._fsdp_layout = layout.FsdpArrangement(mesh, specs)

        def like(tree, shardings):
            return jax.tree_util.tree_map(
                lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree, shardings)

        whole = lambda tree: jax.tree_util.tree_map(lambda _: repl, tree)  # noqa: E731
        opt_sh = [mesh_mod.mirror_opt_shardings(mesh, o, placed[f"layer_{i}"])
                  for i, o in enumerate(opt)]
        shape = (traffic["per_chip_batch"] * chips, cfg["input"]["seq_len"])
        ids = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rows)
        args = (like(params, placed), like(state, whole(state)), like(opt, opt_sh),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
                jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl),
                ids, ids, None, None)
        step = net._build_train_step()
        with mock.patch("jax.default_backend", return_value="tpu"), jax.set_mesh(mesh):
            return step.lower(*args).compile()
    finally:
        dtypes.set_mixed_precision(False)


@pytest.fixture(scope="module")
def compiled(topo):  # noqa: F811
    cfg = load("configs", "mellum2-12b-a2.5b-l4")
    return compile_mesh_step(topo, cfg, load("traffic", "train_ids_mesh_t8192_b1"))


def test_mellum2_step_fits_a_chip_of_four(compiled):
    total = step_bytes(compiled)
    m = compiled.memory_analysis()
    print(f"mellum2 step: {total} bytes a device; arguments {m.argument_size_in_bytes} "
          f"outputs {m.output_size_in_bytes} aliased {m.alias_size_in_bytes} "
          f"temporaries {m.temp_size_in_bytes}")
    assert 0.25 * 16e9 < total < 15.4e9, total
    # weights and Adam's two moments of a chip's 595 153 152 parameters
    assert 7.1e9 < m.argument_size_in_bytes < 7.2e9


def test_mellum2_step_exchanges_and_gathers_no_expert(compiled):
    text = compiled.as_text()
    # 4 expert layers: out and back, forward, recompute and backward
    assert len(re.findall(r"= \S+ all-to-all(?:-start)?\(", text)) >= 16
    assert "all-reduce" in text
    gathers = re.findall(r"= (\S+) all-gather(?:-start)?\(", text)
    assert not [g for g in gathers if re.search(r"\[64,2304,1792\]|\[64,896,2304\]", g)], gathers
    assert re.search(r"(f32|bf16)\[16,2304,1792\]", text)        # a chip's 16 experts
    assert not re.search(r"(f32|bf16)\[64,2304,1792\]", text)
    for part in ("bucket", "exchange/out", "exchange/back", "product", "combine"):
        assert re.search(rf"routedexperts/shard_map/{part}", text), part


def test_mellum2_step_attends_in_bands_and_triangles(compiled):
    text = compiled.as_text()
    for way in ("fwd", "bwd"):
        assert f"dl4j_flash_{way}_bh32_t8192_d128_w1024_" in text     # the sliding layers' band
        assert f"dl4j_flash_{way}_bh32_t8192_d128_bq" in text         # the full layer's triangle
    assert "ragged-dot" in text
    assert re.search(r"bf16\[98304,2048\]", text)               # 4 x 24576 rows, [gate | up]
    assert not re.search(r"(f32|bf16)\[8192,24576\]", text)      # the head in row blocks
    assert not re.search(r"f32\[1,32,8192,8192\]", text)         # no materialised scores
