"""Operations and least bytes of one `kimi-linear-48b-a3b-l5` training step,
from its shapes alone: matrix multiplications (2 FLOPs a weight and token
forward, 6 with the backward), causal latent attention at its PUBLISHED head
widths (keys 192, values 128, whatever a kernel pads them to), and the
per-channel-gated delta rule in its chunked form (chunks of 64). Nothing
recomputed is counted; the routed experts count the EXPECTED assignments of
the experts held (tokens x top-k x held / published), not the buffer's
padding. Norms, the short convolutions, the router's sigmoid and the sort
move bytes, they are not the FLOPs.
"""
from __future__ import annotations

CHUNK = 64      # tokens a chunk of the delta rule (nn/layers/hybrid.py CHUNK)


def kinds(cfg: dict):
    """[(mixer, feed-forward)] a layer built: "kda" | "mla", "dense" | "moe"."""
    kda = set(cfg["linear_attn_config"]["kda_layers"])
    return [("kda" if i in kda else "mla",
             "dense" if i <= cfg["first_k_dense_replace"] else "moe")
            for i in range(1, cfg["num_hidden_layers"] + 1)]


def _count(cfg, kind):
    return sum(kind in pair for pair in kinds(cfg))


def _kda(cfg):
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def _mla(cfg):
    return (cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def layer_parameters(cfg: dict) -> dict:
    """Parameters of one sub-layer of each kind as held here (its pre-norm
    included), the embedding and the head (ISSUE 33's count)."""
    d = cfg["hidden_size"]
    h, dk, cw = _kda(cfg)
    inner = h * dk
    a, rank, nope, rope, vd = _mla(cfg)
    f = cfg["moe_intermediate_size"]
    fs = cfg["num_shared_experts"] * f
    return {
        "kda": (3 * d * inner + 3 * cw * inner + 2 * (d * dk + dk * inner) + h + inner
                + d * h + dk + inner * d + d),
        "mla": (d * a * (nope + rope) + d * (rank + rope) + rank + rank * a * (nope + vd)
                + a * vd * d + d),
        "dense": 3 * d * cfg["intermediate_size"] + d,
        "moe": (d * cfg["num_experts_published"] + cfg["num_experts_published"]
                + 3 * d * fs + cfg["num_experts"] * 3 * d * f + d),
        "embedding": cfg["vocab_size"] * d, "head": d * cfg["vocab_size"], "final_norm": d,
    }


def parameters(cfg: dict) -> int:
    per = layer_parameters(cfg)
    return (sum(per[mixer] + per[ffn] for mixer, ffn in kinds(cfg))
            + per["embedding"] + per["head"] + per["final_norm"])


def matmul_weights_per_token(cfg: dict) -> float:
    """Weights every token is multiplied with, forward, over the layers."""
    d = cfg["hidden_size"]
    h, dk, _ = _kda(cfg)
    inner = h * dk
    kda = 3 * d * inner + d * (2 * dk + h) + 2 * dk * inner + inner * d
    a, rank, nope, rope, vd = _mla(cfg)
    mla = d * a * (nope + rope) + d * (rank + rope) + rank * a * (nope + vd) + a * vd * d
    f = cfg["moe_intermediate_size"]
    held = cfg["num_experts"] / cfg["num_experts_published"]
    moe = (d * cfg["num_experts_published"] + 3 * d * cfg["num_shared_experts"] * f
           + cfg["num_experts_per_token"] * held * 3 * d * f)
    return (_count(cfg, "kda") * kda + _count(cfg, "mla") * mla
            + _count(cfg, "dense") * 3 * d * cfg["intermediate_size"]
            + _count(cfg, "moe") * moe + d * cfg["vocab_size"])   # the embedding gather is free


def attention_flops(cfg: dict, rows: int, seq_len: int) -> int:
    """Forward + backward of causal attention in the latent layers, keys
    dk = nope + rope and values dv wide as published: per token and head
    Q K^T is 2 t dk and P V 2 t dv FLOPs forward, twice that backward,
    halved by the causal mask: 3 t (dk + dv)."""
    a, _, nope, rope, vd = _mla(cfg)
    return _count(cfg, "mla") * rows * seq_len * 3 * seq_len * a * (nope + rope + vd)


def _kda_flops_per_token_head(cfg) -> float:
    """The chunked rule, forward, per token and head, c = CHUNK, keys and
    values dk wide: K K^T and Q K^T with the decay inside (2 c dk each), the
    unit-triangular solve for [U | W] (c (dv + dk)), the chunk's A and B
    (2 dk dk + 2 dk dv), W S and Q S (2 dk dv each), the scores times the
    writes (2 c dv), the scan's product (2 dk dk dv a chunk)."""
    _, dk, _ = _kda(cfg)
    dv, c = dk, CHUNK
    return (4 * c * dk + c * (dv + dk) + 2 * dk * dk + 2 * dk * dv + 4 * dk * dv
            + 2 * c * dv + 2 * dk * dk * dv / c)


def kda_flops(cfg: dict, rows: int) -> int:
    """Forward + backward of the chunked core in every KDA layer, its least
    work whatever implements it."""
    h, _, _ = _kda(cfg)
    return int(3 * _count(cfg, "kda") * rows * cfg["input"]["seq_len"] * h
               * _kda_flops_per_token_head(cfg))


def kda_bytes(cfg: dict, rows: int) -> int:
    """The least that core can move, float32, a chunk's decays and scores
    never leaving the chip: forward reads q, k, v, g (a head's channels each)
    and beta and writes o and the state every chunk starts from; backward
    reads those, do and the states and writes dq, dk, dv, dg, dbeta."""
    h, dk, _ = _kda(cfg)
    inner = h * dk
    state = h * dk * dk / CHUNK                        # floats a token
    inputs = 4 * inner + h
    forward = inputs + inner + state
    backward = inputs + inner + state + inputs
    return int(_count(cfg, "kda") * rows * cfg["input"]["seq_len"] * (forward + backward) * 4)


def step_flops(cfg: dict, rows: int) -> int:
    """One optimizer step on `rows` sequences of the configured length."""
    t = cfg["input"]["seq_len"]
    return int(6 * matmul_weights_per_token(cfg) * rows * t
               + attention_flops(cfg, rows, t) + kda_flops(cfg, rows))


def flash_flops(cfg: dict, rows: int) -> int:
    """What the flash kernels (forward, backward) must compute in a step:
    the latent layers at 32 heads of 192 / 128, t 8192 — the published 192,
    not a padded 256."""
    return attention_flops(cfg, rows, cfg["input"]["seq_len"])


def flash_bytes(cfg: dict, rows: int) -> int:
    """Least HBM traffic of those kernels in bf16: forward reads q, k (dk
    wide) and v and writes o (dv wide); backward reads q, k, v, o, do and
    writes dq, dk, dv."""
    a, _, nope, rope, vd = _mla(cfg)
    dk = nope + rope
    forward, backward = 2 * dk + 2 * vd, 4 * dk + 4 * vd
    return _count(cfg, "mla") * (forward + backward) * rows * cfg["input"]["seq_len"] * a * 2
