"""Operations and least bytes of one `nemotron-3-nano-30b-a3b-l9` training
step, from its shapes alone: matrix multiplications (2 FLOPs a weight and
token forward, 6 with the backward), causal attention, and the scalar-decay
state-space recurrence in its chunked form (chunks of `chunk_size`). Nothing
recomputed is counted; the routed experts count the EXPECTED assignments of
the experts held (tokens x top-k x held / published), not the buffer's
padding. Norms, the short convolution, the router's sigmoid and the sort
move bytes, they are not the FLOPs.
"""
from __future__ import annotations


def _count(cfg, ch):
    return cfg["hybrid_override_pattern"].count(ch)


def _ssm(cfg):
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    return h, p, cfg["n_groups"], cfg["ssm_state_size"], cfg["chunk_size"]


def layer_parameters(cfg: dict) -> dict:
    """Parameters of one layer of each kind as held here, the embedding and
    the head (the table under ISSUE 31's Motivation)."""
    d = cfg["hidden_size"]
    h, p, g, s, _ = _ssm(cfg)
    inner, xbc = h * p, h * p + 2 * g * s
    a, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    f, fs = cfg["moe_intermediate_size"], cfg["moe_shared_expert_intermediate_size"]
    return {
        "M": (d * (inner + xbc + h) + cfg["conv_kernel"] * xbc + xbc + 3 * h + inner
              + inner * d + d),
        "*": d * (a + 2 * kv) * hd + a * hd * d + d,
        "E": (d * cfg["num_experts_published"] + cfg["num_experts_published"]
              + 2 * d * fs + cfg["num_experts"] * 2 * d * f + d),
        "embedding": cfg["vocab_size"] * d, "head": d * cfg["vocab_size"], "final_norm": d,
    }


def parameters(cfg: dict) -> int:
    per = layer_parameters(cfg)
    return (sum(per[ch] for ch in cfg["hybrid_override_pattern"])
            + per["embedding"] + per["head"] + per["final_norm"])


def matmul_weights_per_token(cfg: dict) -> float:
    """Weights every token is multiplied with, forward, over the layers."""
    d = cfg["hidden_size"]
    h, p, g, s, _ = _ssm(cfg)
    inner = h * p
    mamba = d * (2 * inner + 2 * g * s + h) + inner * d
    a, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    attn = d * (a + 2 * kv) * hd + a * hd * d
    held = cfg["num_experts"] / cfg["num_experts_published"]
    moe = (d * cfg["num_experts_published"]
           + 2 * d * cfg["moe_shared_expert_intermediate_size"]
           + cfg["num_experts_per_tok"] * held * 2 * d * cfg["moe_intermediate_size"])
    return (_count(cfg, "M") * mamba + _count(cfg, "*") * attn + _count(cfg, "E") * moe
            + d * cfg["vocab_size"])          # the embedding gather is free


def attention_flops(cfg: dict, rows: int, seq_len: int) -> int:
    """Forward + backward of causal attention in the softmax layers: per
    token QK^T and PV are 4 t (h hd) FLOPs forward, 8 t (h hd) backward,
    halved by the causal mask: 6 t (h hd)."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return _count(cfg, "*") * rows * seq_len * 6 * seq_len * width


def _ssd_flops_per_token_head(cfg) -> float:
    """The chunked recurrence, forward, per token and head: C B^T once a
    group (2 c s, shared by h / g heads), the masked scores times X (2 c p),
    the chunk's own state and the read of the state it starts from (2 p s
    each), the multiply-add of the scan over chunks (2 p s a chunk)."""
    h, p, g, s, c = _ssm(cfg)
    return 2 * c * s * g / h + 2 * c * p + 4 * p * s + 2 * p * s / c


def ssd_flops(cfg: dict, rows: int) -> int:
    """Forward + backward of the chunked core in every state-space layer,
    its least work whatever implements it."""
    h = cfg["mamba_num_heads"]
    return int(3 * _count(cfg, "M") * rows * cfg["input"]["seq_len"] * h
               * _ssd_flops_per_token_head(cfg))


def ssd_bytes(cfg: dict, rows: int) -> int:
    """The least that core can move, float32, a chunk's decays and scores
    never leaving the chip: forward reads x, B, C, dt and writes y and the
    state every chunk starts from; backward reads x, B, C, dt, dy and those
    states and writes dx, dB, dC, ddt."""
    h, p, g, s, c = _ssm(cfg)
    inner, bc = h * p, 2 * g * s
    state = h * p * s / c                              # floats a token
    forward = (inner + bc + h) + inner + state
    backward = (inner + bc + h) + inner + state + (inner + bc + h)
    return int(_count(cfg, "M") * rows * cfg["input"]["seq_len"] * (forward + backward) * 4)


def step_flops(cfg: dict, rows: int) -> int:
    """One optimizer step on `rows` sequences of the configured length."""
    t = cfg["input"]["seq_len"]
    return int(6 * matmul_weights_per_token(cfg) * rows * t
               + attention_flops(cfg, rows, t) + ssd_flops(cfg, rows))


def flash_flops(cfg: dict, rows: int) -> int:
    """What the flash kernels (forward, backward) must compute in a step:
    the softmax layers at 32 heads of 128, t 8192."""
    return attention_flops(cfg, rows, cfg["input"]["seq_len"])


def flash_bytes(cfg: dict, rows: int) -> int:
    """Least HBM traffic of those kernels in bf16, with the key/value heads
    as the kernel is handed them (repeated to the query heads): forward
    reads q, k, v and writes o; backward reads q, k, v, o, do and writes
    dq, dk, dv."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return _count(cfg, "*") * (4 + 8) * rows * cfg["input"]["seq_len"] * width * 2
