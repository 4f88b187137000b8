"""How a Pallas kernel call runs where it is traced.

A pallas_call lowers to a custom call GSPMD has no partitioning rule
for. Fed a batch-sharded operand under a TPU mesh it does not lower at
all ("Mosaic kernels cannot be automatically partitioned"); interpreted
on the CPU it silently gathers the batch onto every device. So the
multi-device entry points (ParallelWrapper's step calls, the serving
dispatch) call their jitted functions under `jax.set_mesh(mesh)`, and
every kernel family's entry function in ops/ asks here how the call must
run. The ambient mesh is part of jit's trace-cache key, so a step first
traced on one device retraces when it is next called under a mesh.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
from jax.sharding import PartitionSpec as P


def interpret() -> bool:
    """The `interpret` flag of every pallas_call: off the TPU a kernel
    runs in Pallas's interpreter (the CPU tests)."""
    return jax.default_backend() != "tpu"


def _kernel_batch_shards() -> Optional[int]:
    """1 — a kernel traced here is called directly (no ambient mesh, one
    device, or already inside a shard_map body that is manual over every
    >1 axis); n — each of the n devices on the 'data' axis runs it on its
    own rows; None — the ambient mesh shards a non-batch axis under GSPMD
    (model/fsdp/...), which no kernel here can follow."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return 1
    auto = [a for a in am.axis_names
            if am.shape[a] > 1 and a not in am.manual_axes]
    if not auto:
        return 1
    if auto == ["data"]:
        return int(am.shape["data"])
    return None


def per_device_batch(batch: int) -> int:
    """Rows of a `batch`-row operand that ONE device's kernel call sees
    when traced here — what block plans and admission regimes must be
    judged on. 0 when no kernel can run here (the mesh shards something
    other than the batch, or the batch does not split evenly): the caller
    keeps its XLA formulation."""
    shards = _kernel_batch_shards()
    return batch // shards if shards and batch % shards == 0 else 0


def per_batch_shard(fn, args: Sequence[Any], batched: Sequence[bool]):
    """`fn(*args)` the way a kernel must run here: directly, or with each
    device on the 'data' axis running it on its own rows — args flagged
    in `batched` (and every output) split axis 0 over 'data', the rest
    (weights) arrive whole, and shard_map's transpose psums their
    cotangents over the shards."""
    shards = _kernel_batch_shards()
    if shards is None:
        raise ValueError(
            f"a Pallas kernel cannot run under the ambient mesh "
            f"{dict(jax.sharding.get_abstract_mesh().shape)}: kernels "
            f"run per 'data' shard only")
    if shards == 1:
        return fn(*args)
    specs = tuple(P("data") if b else P() for b in batched)
    # manual over EVERY axis still automatic here: besides 'data' they all
    # have size 1, so it is the same program — but a shard_map manual over
    # 'data' alone makes XLA:CPU abort ("Invalid binary instruction opcode
    # copy") when it psums a bf16 weight cotangent (jax/jaxlib 0.9.0)
    am = jax.sharding.get_abstract_mesh()
    return jax.shard_map(
        fn, in_specs=specs, out_specs=P("data"),
        axis_names=set(am.axis_names) - set(am.manual_axes),
        check_vma=False)(*args)
