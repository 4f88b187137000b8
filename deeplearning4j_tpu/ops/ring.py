"""Ring attention: exact attention over a sequence-sharded mesh axis.

The reference framework's only long-sequence mechanism is truncated BPTT
(SURVEY.md §5 — no attention, no context parallelism; 2017-era). Ring
attention is the TPU-native long-context capability the north star requires:
shard the sequence over a mesh axis, keep Q local, and rotate K/V blocks
around the ring with `lax.ppermute` so each device accumulates the exact
softmax over the FULL sequence using the online (flash) recurrence from
ops/attention.py. Peak memory per chip is O(t/n_shards · d) and the K/V
transfer rides ICI neighbor links — the collective-friendly layout the
scaling playbook prescribes (PAPERS.md: Ring Attention, Liu et al. 2023).

Causal masking uses global block offsets derived from `lax.axis_index`, so a
device skips (contributes zeros for) key blocks entirely in its future.

Two entry points:
  ring_attention_sharded — per-shard function, call INSIDE an existing
      shard_map whose mesh has the sequence axis. This is what
      `ops.attention.attend` (the attention layers' one entry) dispatches
      to when `sequence_parallel` is active.
  ring_attention — convenience wrapper that builds the shard_map over a mesh
      for standalone use/testing.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.ops import attention as att

_tls = threading.local()


@contextlib.contextmanager
def sequence_parallel(axis_name: str = "seq"):
    """While active (during tracing), the attention layers compute ring
    attention over `axis_name` instead of local SDPA. The enclosing
    computation must be shard_mapped over a mesh containing that axis with
    activations sharded [batch, time/axis, features]."""
    prev = getattr(_tls, "seq_axis", None)
    _tls.seq_axis = axis_name
    try:
        yield
    finally:
        _tls.seq_axis = prev


def active_sequence_axis() -> Optional[str]:
    return getattr(_tls, "seq_axis", None)


def _hop_update(acc, q, k_cur, v_cur, m_cur, *, scale, causal, q_off,
                k_off, block_size):
    """Accumulate one ring hop's K/V into the online-softmax state.

    Without block_size (or when the hop fits in one block) this is a
    single online_block — which materializes [b, h, t_loc, t_loc]
    scores. With block_size, the hop runs the shared flash inner loop
    (ops.attention.online_chunks: lax.scan over K/V sub-chunks with
    ragged tails padded and masked dead), so per-hop peak memory drops
    to [b, h, t_loc, block_size] — a second level of blocking, making
    LONG per-device shards (t_loc in the tens of thousands)
    trainable."""
    t_loc = k_cur.shape[2]
    if block_size is None or t_loc <= block_size:
        return att.online_block(
            acc, q, k_cur, v_cur, scale=scale, mask_blk=m_cur,
            causal=causal, q_offset=q_off, k_offset=k_off)
    return att.online_chunks(acc, q, k_cur, v_cur, scale=scale,
                             mask=m_cur, causal=causal, q_offset=q_off,
                             k_offset=k_off, block_size=block_size)


def ring_attention_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    mask: Optional[jnp.ndarray] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    block_size: Optional[int] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Exact attention where q/k/v are the LOCAL sequence shards
    [b, h, t_loc, d] of a sequence sharded over `axis_name`.

    Rotates K/V (and the key-padding mask) one ring hop per step; after
    n_shards steps every device has accumulated the full-softmax output
    for its local queries. `block_size` additionally chunks each hop's
    K/V (see _hop_update) so per-chip attention memory is
    O(t_loc · block_size) instead of O(t_loc²).

    A `window` is refused: the ring sends every key/value shard past every
    device, where a band of `window` keys needs the hops its queries see
    and no other (ROADMAP R7).
    """
    if window is not None:
        raise NotImplementedError(
            f"ring attention has no window (window={window}): every hop's keys "
            f"go round the whole ring; run the windowed layer without a "
            f"sequence axis (ROADMAP R7)")
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    t_loc = q.shape[2]
    d = q.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    perm = [(j, (j + 1) % n) for j in range(n)]

    q_off = idx * t_loc
    acc = att.online_init(q, v.shape[-1])
    k_cur, v_cur = k, v
    m_cur = mask
    # n is a static mesh-axis size: a Python loop unrolls into n ppermute +
    # online-softmax stages that XLA can overlap (compute hides ICI latency).
    for s in range(n):
        src = (idx - s) % n          # which global block we currently hold
        k_off = src * t_loc
        acc = _hop_update(acc, q, k_cur, v_cur, m_cur, scale=scale,
                          causal=causal, q_off=q_off, k_off=k_off,
                          block_size=block_size)
        if s != n - 1:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
            if m_cur is not None:
                m_cur = lax.ppermute(m_cur, axis_name, perm)
    # same output-dtype contract as ops.attention primitives: q.dtype
    return att.online_finish(acc).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    axis_name: str = "seq",
    mask: Optional[jnp.ndarray] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    block_size: Optional[int] = None,
) -> jnp.ndarray:
    """Standalone ring attention over GLOBAL arrays q/k/v [b, h, t, d]:
    shards the time axis over `axis_name`, runs the ring, gathers back."""
    qs = P(None, None, axis_name, None)  # jaxlint: disable=JX018 — axis_name is caller-chosen; a SpecLayout rule can't name it
    ms = P(None, axis_name)  # jaxlint: disable=JX018 — same caller-chosen axis
    in_specs = (qs, qs, qs) + ((ms,) if mask is not None else ())
    args = (q, k, v) + ((mask,) if mask is not None else ())

    def body(*xs):
        if mask is not None:
            ql, kl, vl, ml = xs
        else:
            (ql, kl, vl), ml = xs, None
        return ring_attention_sharded(
            ql, kl, vl, axis_name=axis_name, mask=ml, causal=causal,
            scale=scale, block_size=block_size,
        )

    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=qs,
        check_vma=False,
    )(*args)
