"""Operations and least bytes of one `gpt2-small` training step, from its
shapes alone. Matrix multiplications only (LayerNorm, softmax, GELU and the
loss move bytes, they are not the FLOPs); nothing recomputed is counted.

The weight count follows `profile_transformer.transformer_step_flops`. Its
attention term does not: that function counts 2 t d multiply-adds per token
for QK^T where there are t d (h heads x t keys x d/h each), so it is twice
too high. Here, per token and layer, QK^T and PV are 2 t d multiply-adds =
4 t d FLOPs forward; backward needs four such products (dV, dP, dQ, dK) =
8 t d; causal masking halves all of it: 6 t d.
"""
from __future__ import annotations


def matmul_weights(cfg: dict) -> int:
    d, v, n = cfg["n_embd"], cfg["vocab_size"], cfg["n_layer"]
    per_layer = d * 3 * d + d * d + 2 * (d * 4 * d)
    return n * per_layer + d * v          # the embedding gather is free


def attention_flops(cfg: dict, rows: int, seq_len: int) -> int:
    """Forward + backward of causal attention in every layer."""
    d, n = cfg["n_embd"], cfg["n_layer"]
    return n * rows * seq_len * 6 * seq_len * d


def step_flops(cfg: dict, rows: int) -> int:
    """One optimizer step on `rows` sequences of the configured length."""
    t = cfg["input"]["seq_len"]
    return 6 * matmul_weights(cfg) * rows * t + attention_flops(cfg, rows, t)


def flash_flops(cfg: dict, rows: int) -> int:
    """What the flash kernels (forward, dq, dkv) must compute in a step."""
    return attention_flops(cfg, rows, cfg["input"]["seq_len"])


def flash_bytes(cfg: dict, rows: int) -> int:
    """Least HBM traffic of those kernels in bf16: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    t, d, n = cfg["input"]["seq_len"], cfg["n_embd"], cfg["n_layer"]
    return n * (4 + 8) * rows * t * d * 2
