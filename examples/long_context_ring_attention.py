"""Long-context training with ring-attention sequence parallelism.

Run (8 virtual CPU devices; on a real slice drop the env overrides):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/long_context_ring_attention.py [--seq 4096]

A sequence far longer than one device would want to hold is sharded over
the mesh's `seq` axis: every device keeps 1/seq_shards of the tokens, and
exact causal attention is computed by rotating K/V blocks one ICI hop per
ring step (ops/ring.py) — no approximation, O(t/n) activation memory
per device. The same ShardedTransformerLM composes the ring with data and
tensor parallelism (docs/PARALLELISM.md).
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args()

    import jax

    from deeplearning4j_tpu.parallel import MeshSpec, build_mesh
    from deeplearning4j_tpu.parallel.transformer import (
        ShardedTransformerLM,
        TransformerConfig,
    )

    n = len(jax.devices())
    # largest proper divisor of n as the seq axis (1 for primes/1 device)
    seq_shards = next((d for d in range(n // 2, 0, -1) if n % d == 0), 1)
    data_shards = n // seq_shards
    if args.seq % seq_shards:
        raise SystemExit(f"--seq {args.seq} must divide by the "
                         f"{seq_shards}-way seq axis")
    mesh = build_mesh(MeshSpec(data=data_shards, seq=seq_shards))
    print(f"{n} devices -> data={data_shards} x seq={seq_shards}; "
          f"each device holds {args.seq // seq_shards} of {args.seq} tokens")

    cfg = TransformerConfig(vocab=512, d_model=64, n_heads=4, n_layers=2,
                            max_len=args.seq, remat=True)
    lm = ShardedTransformerLM(cfg, mesh).init(seed=0)

    rng = np.random.default_rng(0)
    b = 2 * data_shards
    ids = rng.integers(0, cfg.vocab, (b, args.seq)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1)

    losses = []
    t0 = time.perf_counter()
    for step in range(args.steps):
        loss = lm.fit_batch(ids, tgt)
        losses.append(float(loss))
        print(f"step {step}: loss {losses[-1]:.4f}")
    dt = time.perf_counter() - t0
    if args.steps > 1:
        assert losses[-1] < losses[0], "loss should decrease"
    print(f"{b * args.seq * args.steps / dt:.0f} tokens/s over "
          f"{args.seq}-token sequences (incl. compile)")


if __name__ == "__main__":
    main()
