"""Operations and least bytes of one `qwen3-next-80b-a3b-l4` training step,
from its shapes alone: matrix multiplications (2 FLOPs a weight and token
forward, 6 with the backward), causal attention, and the gated delta rule in
its chunked form (chunks of 64). Nothing recomputed is counted; the routed
experts count the EXPECTED assignments of the experts held (tokens x top-k x
held / published), not the buffer's padding. Norms, the short convolution,
the softmax over experts and the sort move bytes, they are not the FLOPs.
"""
from __future__ import annotations

CHUNK = 64


def _kinds(cfg):
    n, k = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    attn = sum(1 for i in range(n) if (i + 1) % k == 0)
    return n - attn, attn


def matmul_weights_per_token(cfg: dict) -> float:
    """Weights every token is multiplied with, forward, over the layers."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    n_delta, n_attn = _kinds(cfg)
    key = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    val = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    delta = d * (2 * key + 2 * val) + d * 2 * cfg["linear_num_value_heads"] + val * d
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    attn = d * (2 * h + 2 * kv) * hd + h * hd * d
    held = cfg["num_experts"] / cfg["num_experts_published"]
    moe = (d * cfg["num_experts_published"]
           + 3 * d * cfg["shared_expert_intermediate_size"] + d
           + cfg["num_experts_per_tok"] * held * 3 * d * cfg["moe_intermediate_size"])
    return (n_delta * delta + n_attn * attn
            + cfg["num_hidden_layers"] * moe + d * v)   # the embedding gather is free


def attention_flops(cfg: dict, rows: int, seq_len: int) -> int:
    """Forward + backward of causal attention in the softmax layers: per
    token QK^T and PV are 4 t (h hd) FLOPs forward, 8 t (h hd) backward,
    halved by the causal mask: 6 t (h hd)."""
    _, n_attn = _kinds(cfg)
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return n_attn * rows * seq_len * 6 * seq_len * width


def _scan_flops_per_token_head(cfg) -> int:
    """The recurrence across chunks, forward, per token and value head:
    S' = A S + B, one [dk, dk] x [dk, dv] product a chunk (2 dk dk dv / c =
    4 dk dv at c = dk / 2, which is also what the factored form W S, K^T D
    costs)."""
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return 2 * dk * dk * dv // CHUNK


def delta_rule_flops(cfg: dict, rows: int, seq_len: int) -> int:
    """Forward + backward of the chunked delta rule in the delta layers, in
    its leanest form: the recurrence, the reads Q S and W S of the state
    (2 dk dv each), QK D (2 c dv), K K^T and Q K^T within the chunk (2 c dk
    each) and the unit-triangular solve for dv + dk columns."""
    n_delta, _ = _kinds(cfg)
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    per = (_scan_flops_per_token_head(cfg) + 4 * dk * dv + 2 * CHUNK * dv
           + 4 * CHUNK * dk + CHUNK * (dk + dv))
    return 3 * n_delta * rows * seq_len * cfg["linear_num_value_heads"] * per


def step_flops(cfg: dict, rows: int) -> int:
    """One optimizer step on `rows` sequences of the configured length."""
    t = cfg["input"]["seq_len"]
    return int(6 * matmul_weights_per_token(cfg) * rows * t
               + attention_flops(cfg, rows, t) + delta_rule_flops(cfg, rows, t))


def flash_flops(cfg: dict, rows: int) -> int:
    """What the flash kernels (forward, dq, dkv) must compute in a step:
    the one softmax layer at 16 heads of 256, t 8192."""
    return attention_flops(cfg, rows, cfg["input"]["seq_len"])


def flash_bytes(cfg: dict, rows: int) -> int:
    """Least HBM traffic of those kernels in bf16, with the key/value heads
    as the kernel is handed them (repeated to the query heads): forward
    reads q, k, v and writes o; backward reads q, k, v, o, do and writes
    dq, dk, dv."""
    _, n_attn = _kinds(cfg)
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return n_attn * (4 + 8) * rows * cfg["input"]["seq_len"] * width * 2


def gdn_flops(cfg: dict, rows: int) -> int:
    """Forward + backward of the chunk rule in every delta layer, its least
    work whatever implements it (`delta_rule_flops` at the configured
    length)."""
    return delta_rule_flops(cfg, rows, cfg["input"]["seq_len"])


def gdn_bytes(cfg: dict, rows: int) -> int:
    """The least that rule can move, float32 as the configuration runs it, a
    chunk's decays, scores, inverse and writes never leaving the chip and
    q, k read once a KEY head: forward reads q, k (key heads), v, the decay g
    and beta (a value head each) and writes o and the state every chunk
    starts from; backward reads those, do and the states and writes dq, dk,
    dv, dg, dbeta. Nothing recomputed: the forward a checkpoint runs again
    is waste the roofline shows. (`kda_bytes` and `ssd_bytes` count the same
    way.)"""
    n_delta, _ = _kinds(cfg)
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    state = hv * dk * dv / CHUNK                       # floats a token
    inputs = 2 * hk * dk + hv * dv + 2 * hv
    forward = inputs + hv * dv + state
    backward = inputs + hv * dv + state + inputs
    return int(n_delta * rows * cfg["input"]["seq_len"] * (forward + backward) * 4)
