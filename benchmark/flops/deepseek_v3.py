"""Operations and least bytes of one `deepseek_v3` training step
(`kanana-2-30b-a3b-l5`), from its shapes alone: matrix multiplications
(2 FLOPs a weight and token forward, 6 with the backward) and causal latent
attention at its PUBLISHED head widths (keys 192, values 128, whatever a
kernel pads them to) in every layer. Nothing recomputed is counted; the
routed experts count the EXPECTED assignments of the experts held (tokens x
top-k x held / published), not the buffer's padding. Norms, the rotation,
the router's sigmoid and the sort move bytes, they are not the FLOPs.
"""
from __future__ import annotations


def kinds(cfg: dict):
    """The feed-forward of each layer built: "dense" | "moe" (every mixer is
    latent attention)."""
    return ["dense" if i <= cfg["first_k_dense_replace"] else "moe"
            for i in range(1, cfg["num_hidden_layers"] + 1)]


def _mla(cfg):
    return (cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def _mla_weights(cfg) -> int:
    """q, [c | kr], [k_nope | v], o: the matrices of one latent mixer."""
    d = cfg["hidden_size"]
    a, rank, nope, rope, vd = _mla(cfg)
    return d * a * (nope + rope) + d * (rank + rope) + rank * a * (nope + vd) + a * vd * d


def layer_parameters(cfg: dict) -> dict:
    """Parameters of one sub-layer of each kind as held here (its pre-norm
    included), the embedding and the head (ISSUE 38's count)."""
    d = cfg["hidden_size"]
    f = cfg["moe_intermediate_size"]
    published = cfg["n_routed_experts_published"]
    return {
        "mla": _mla_weights(cfg) + cfg["kv_lora_rank"] + d,
        "dense": 3 * d * cfg["intermediate_size"] + d,
        "moe": (d * published + published + 3 * d * cfg["n_shared_experts"] * f
                + cfg["n_routed_experts"] * 3 * d * f + d),
        "embedding": cfg["vocab_size"] * d, "head": d * cfg["vocab_size"], "final_norm": d,
    }


def parameters(cfg: dict) -> int:
    per = layer_parameters(cfg)
    return (sum(per["mla"] + per[ffn] for ffn in kinds(cfg))
            + per["embedding"] + per["head"] + per["final_norm"])


def matmul_weights_per_token(cfg: dict) -> float:
    """Weights every token is multiplied with, forward, over the layers."""
    d = cfg["hidden_size"]
    f = cfg["moe_intermediate_size"]
    held = cfg["n_routed_experts"] / cfg["n_routed_experts_published"]
    moe = (d * cfg["n_routed_experts_published"] + 3 * d * cfg["n_shared_experts"] * f
           + cfg["num_experts_per_tok"] * held * 3 * d * f)
    n = kinds(cfg)
    return (len(n) * _mla_weights(cfg) + n.count("dense") * 3 * d * cfg["intermediate_size"]
            + n.count("moe") * moe + d * cfg["vocab_size"])   # the embedding gather is free


def attention_flops(cfg: dict, rows: int, seq_len: int) -> int:
    """Forward + backward of causal attention in every layer, keys
    dk = nope + rope and values dv wide as published: per token and head
    Q K^T is 2 t dk and P V 2 t dv FLOPs forward, twice that backward,
    halved by the causal mask: 3 t (dk + dv)."""
    a, _, nope, rope, vd = _mla(cfg)
    return cfg["num_hidden_layers"] * rows * seq_len * 3 * seq_len * a * (nope + rope + vd)


def step_flops(cfg: dict, rows: int) -> int:
    """One optimizer step on `rows` sequences of the configured length."""
    t = cfg["input"]["seq_len"]
    return int(6 * matmul_weights_per_token(cfg) * rows * t + attention_flops(cfg, rows, t))


def flash_flops(cfg: dict, rows: int) -> int:
    """What the flash kernels (forward, backward) must compute in a step:
    every layer at 32 heads of 192 / 128, t 8192 — the published 192, not a
    padded 256."""
    return attention_flops(cfg, rows, cfg["input"]["seq_len"])


def flash_bytes(cfg: dict, rows: int) -> int:
    """Least HBM traffic of those kernels in bf16: forward reads q, k (dk
    wide) and v and writes o (dv wide); backward reads q, k, v, o, do and
    writes dq, dk, dv."""
    a, _, nope, rope, vd = _mla(cfg)
    dk = nope + rope
    forward, backward = 2 * dk + 2 * vd, 4 * dk + 4 * vd
    return (cfg["num_hidden_layers"] * (forward + backward) * rows
            * cfg["input"]["seq_len"] * a * 2)
