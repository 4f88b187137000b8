"""The one place where the benchmark touches the system under test.

Builds the zoo model a configuration file names, installs the benchmark's
seeded weights into it, and reads the program's parameters and optimizer
state back in the reference's flat naming. Everything here goes through the
package's public entry points (`zoo`, `models`, `dtypes`, `ParallelWrapper`,
`InferenceServer`); the attributes it reads (`net.params`, `net.state`,
`net.opt_state`) are the ones `chip_smoke.py` reads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def model(cfg: dict):
    """`zoo.<class>(**args)` as the configuration's `program` gives them:
    the model's description, no array and no device."""
    from deeplearning4j_tpu import zoo

    prog = cfg["program"]
    args = {k: tuple(v) if isinstance(v, list) else v
            for k, v in prog["args"].items()}
    return getattr(zoo, prog["zoo"])(**args)


def build_net(cfg: dict):
    """The model with the configuration's precision policy and learning
    rate, initialised by the program (the weights are replaced by
    `install`)."""
    from deeplearning4j_tpu import dtypes
    from deeplearning4j_tpu.models import ComputationGraph, MultiLayerNetwork
    from deeplearning4j_tpu.nn.graph_conf import ComputationGraphConfiguration

    prog = cfg["program"]
    if prog["precision"] not in ("mixed_bf16", "float32"):
        raise ValueError(f"unknown precision {prog['precision']!r}")
    dtypes.set_mixed_precision(prog["precision"] == "mixed_bf16")
    conf = model(cfg).conf()
    lr = cfg["optimizer"]["args"]["learning_rate"]
    conf.defaults.updater.learning_rate = lr
    graph = isinstance(conf, ComputationGraphConfiguration)
    return (ComputationGraph if graph else MultiLayerNetwork)(conf).init()


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _set(tree, path, value):
    for k in path[:-1]:
        tree = tree[k]
    tree[path[-1]] = value


def _copy_tree(tree):
    return jax.tree_util.tree_map(lambda a: a, tree)


def install(net, ref_mod, cfg, params: dict, state: dict) -> None:
    """Replace the program's initial weights (and running statistics) by
    the benchmark's. Every program leaf must be named by the reference's
    mapping with the same shape: a leaf left over, or a shape that differs,
    is an error, not a default. The arrays are copied: the train step
    donates its inputs and the reference's must outlive it."""
    new_params = _copy_tree(net.params)
    paths = ref_mod.program_paths(cfg)
    for name, path in paths.items():
        old = _get(new_params, path)
        if old.shape != params[name].shape:
            raise ValueError(f"{name} -> {path}: reference {params[name].shape}"
                             f" program {old.shape}")
        _set(new_params, path, jnp.array(params[name], dtype=old.dtype, copy=True))
    n_prog = len(jax.tree_util.tree_leaves(net.params))
    if n_prog != len(paths):
        raise ValueError(f"program has {n_prog} parameter leaves, the "
                         f"reference names {len(paths)}")
    net.params = new_params
    if state:
        new_state = _copy_tree(net.state)
        spaths = ref_mod.program_state_paths(cfg)
        for name, path in spaths.items():
            old = _get(new_state, path)
            if old.shape != state[name].shape:
                raise ValueError(f"state {name} -> {path}: shape")
            _set(new_state, path, jnp.array(state[name], dtype=old.dtype, copy=True))
        if len(jax.tree_util.tree_leaves(net.state)) != len(spaths):
            raise ValueError("program state leaves not all named")
        net.state = new_state


def read_params(net, ref_mod, cfg) -> dict:
    """The program's parameters in the reference's naming (no copy)."""
    return {name: _get(net.params, path)
            for name, path in ref_mod.program_paths(cfg).items()}


def read_opt_slot(net, ref_mod, cfg, slot: str) -> dict:
    """One slot of the optimizer state ("m" for Adam, "v" for Nesterovs)
    per reference leaf. MultiLayerNetwork keeps a list per layer,
    ComputationGraph a dict per vertex."""
    out = {}
    for name, path in ref_mod.program_paths(cfg).items():
        layer = path[0]
        o = net.opt_state
        node = o[int(layer.split("_")[1])] if isinstance(o, list) else o[layer]
        out[name] = _get(node[slot], path[1:])
    return out


def wrapper(net, chips: int):
    from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper

    return ParallelWrapper(net, mesh_spec=MeshSpec(data=chips))


def dataset(x, y):
    from deeplearning4j_tpu.datasets.dataset import DataSet

    return DataSet(x, y)


def iterator_base():
    from deeplearning4j_tpu.datasets.iterators import DataSetIterator

    return DataSetIterator


def server(net, mesh, traffic: dict, warm_example):
    """`InferenceServer` as the traffic file configures it; `wait_ms` is
    left at the server's default."""
    from deeplearning4j_tpu.serving import InferenceServer
    from deeplearning4j_tpu.serving.buckets import BucketSpec

    sizes = tuple(traffic["buckets"])
    n = mesh.shape["data"]
    return InferenceServer(
        model=net, mesh=mesh, batch_limit=traffic["batch_limit"],
        queue_limit=traffic["queue_limit"],
        buckets=BucketSpec(max(sizes), align=n, sizes=sizes),
        default_deadline_s=traffic["deadline_s"], warmup_example=warm_example)


def serving_errors():
    from deeplearning4j_tpu.serving.errors import ServingError

    return ServingError
