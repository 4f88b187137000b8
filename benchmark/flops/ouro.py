"""Operations and least bytes of one `ouro` training step (`ouro-2.6b-l6`),
from its shapes alone: matrix multiplications (2 FLOPs a weight and token
forward, 6 with the backward) and causal multi-head attention at its
published head width — every layer `total_ut_steps` times, because the loop
runs the SAME layers again, and the head as often, because the loss reads
the state of every pass. Nothing recomputed is counted. The four norms a
layer and pass, the rotation, the gate's dot product a token and pass and
the exit distribution move bytes, they are not the FLOPs.
"""
from __future__ import annotations


def layer_parameters(cfg: dict) -> dict:
    """Parameters of ONE pass (the only ones there are), by kind."""
    d, h, kv, hd = (cfg[k] for k in ("hidden_size", "num_attention_heads",
                                     "num_key_value_heads", "head_dim"))
    return {
        "attention": d * (h + 2 * kv) * hd + h * hd * d,
        "mlp": 3 * d * cfg["intermediate_size"],
        "norm": d,
        "embedding": cfg["vocab_size"] * d, "head": d * cfg["vocab_size"],
        "gate": d + 1,
    }


def parameters(cfg: dict) -> int:
    per = layer_parameters(cfg)
    return (cfg["num_hidden_layers"] * (per["attention"] + per["mlp"] + 4 * per["norm"])
            + per["embedding"] + per["head"] + per["norm"] + per["gate"])


def matmul_weights_per_token(cfg: dict) -> float:
    """Weights every token is multiplied with, forward, over a step's passes:
    the layers and the head `total_ut_steps` times (the embedding gather is
    free, the gate's vector is 2048 weights a pass: counted)."""
    per = layer_parameters(cfg)
    one_pass = (cfg["num_hidden_layers"] * (per["attention"] + per["mlp"])
                + per["head"] + cfg["hidden_size"])
    return cfg["total_ut_steps"] * one_pass


def attention_flops(cfg: dict, rows: int, seq_len: int) -> int:
    """Forward + backward of causal attention in every layer of every pass,
    16 heads of 128: per token and head Q K^T and P V are 2 t d FLOPs each
    forward, twice that backward, halved by the causal mask: 6 t d."""
    applications = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    return (applications * rows * seq_len * 6 * seq_len
            * cfg["num_attention_heads"] * cfg["head_dim"])


def step_flops(cfg: dict, rows: int) -> int:
    """One optimizer step on `rows` sequences of the configured length."""
    t = cfg["input"]["seq_len"]
    return int(6 * matmul_weights_per_token(cfg) * rows * t + attention_flops(cfg, rows, t))


def flash_flops(cfg: dict, rows: int) -> int:
    """What the flash kernels (forward, backward) must compute in a step:
    24 layer applications at 16 heads of 128, t 8192."""
    return attention_flops(cfg, rows, cfg["input"]["seq_len"])


def flash_bytes(cfg: dict, rows: int) -> int:
    """Least HBM traffic of those kernels in bf16: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv — keys and
    values at their own 16 heads (as many as the queries: nothing repeated)."""
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    applications = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    forward, backward = 2 * h + 2 * kv, 4 * h + 4 * kv
    return (applications * (forward + backward) * rows * cfg["input"]["seq_len"]
            * cfg["head_dim"] * 2)
