"""Operations and least bytes of one `lfm2_moe` training step
(`lfm2-24b-a2b-l5`), from its shapes alone: matrix multiplications
(2 FLOPs a weight and token forward, 6 with the backward) and causal
grouped-query attention at its published head width in the attention
layers. Nothing recomputed is counted; the routed experts count the EXPECTED
assignments of the experts held (tokens x top-k x held / published), not the
buffer's padding. Norms, the rotation, the two gates and the three-tap
convolution of a conv mixer, the router's sigmoid and the sort move bytes,
they are not the FLOPs.
"""
from __future__ import annotations


def kinds(cfg: dict):
    """(mixer, feed-forward) of each layer built: ("conv" | "attention",
    "dense" | "moe"), the published layers `layers_first` .. on."""
    first = cfg.get("layers_first", 0)
    return [("conv" if cfg["layer_types"][i] == "conv" else "attention",
             "dense" if i < cfg["num_dense_layers"] else "moe")
            for i in range(first, first + cfg["num_hidden_layers"])]


def _heads(cfg):
    h = cfg["num_attention_heads"]
    return h, cfg["num_key_value_heads"], cfg["hidden_size"] // h


def _mixer_weights(cfg) -> dict:
    """The matrices of one mixer of each kind: Win [d, 3 d] and Wout [d, d];
    [q | k | v] and o."""
    d = cfg["hidden_size"]
    h, kv, hd = _heads(cfg)
    return {"conv": 4 * d * d, "attention": d * (h + 2 * kv) * hd + h * hd * d}


def layer_parameters(cfg: dict) -> dict:
    """Parameters of one sub-layer of each kind as held here, WITHOUT its
    pre-norm (ISSUE 40's count lists the norms apart), the embedding and the
    head."""
    d = cfg["hidden_size"]
    f = cfg["moe_intermediate_size"]
    published = cfg["num_experts_published"]
    mix = _mixer_weights(cfg)
    return {
        "conv": mix["conv"] + cfg["conv_L_cache"] * d,
        "attention": mix["attention"] + 2 * _heads(cfg)[2],
        "dense": 3 * d * cfg["intermediate_size"],
        "moe": d * published + published + cfg["num_experts"] * 3 * d * f,
        "norm": d,
        "embedding": cfg["vocab_size"] * d, "head": d * cfg["vocab_size"],
    }


def parameters(cfg: dict) -> int:
    per = layer_parameters(cfg)
    return (sum(per[mixer] + per[ffn] + 2 * per["norm"] for mixer, ffn in kinds(cfg))
            + per["embedding"] + per["head"] + per["norm"])


def matmul_weights_per_token(cfg: dict) -> float:
    """Weights every token is multiplied with, forward, over the layers."""
    d = cfg["hidden_size"]
    f = cfg["moe_intermediate_size"]
    held = cfg["num_experts"] / cfg["num_experts_published"]
    mix = _mixer_weights(cfg)
    ffn = {"dense": 3 * d * cfg["intermediate_size"],
           "moe": d * cfg["num_experts_published"]
           + cfg["num_experts_per_tok"] * held * 3 * d * f}
    return (sum(mix[mixer] + ffn[kind] for mixer, kind in kinds(cfg))
            + d * cfg["vocab_size"])                  # the embedding gather is free


def attention_flops(cfg: dict, rows: int, seq_len: int) -> int:
    """Forward + backward of causal attention in the attention layers, 32
    query heads of 64 (each key/value head is read by four of them): per
    token and head Q K^T and P V are 2 t d FLOPs each forward, twice that
    backward, halved by the causal mask: 6 t d."""
    h, _, hd = _heads(cfg)
    layers = sum(mixer == "attention" for mixer, _ in kinds(cfg))
    return layers * rows * seq_len * 6 * seq_len * h * hd


def step_flops(cfg: dict, rows: int) -> int:
    """One optimizer step on `rows` sequences of the configured length."""
    t = cfg["input"]["seq_len"]
    return int(6 * matmul_weights_per_token(cfg) * rows * t + attention_flops(cfg, rows, t))


def flash_flops(cfg: dict, rows: int) -> int:
    """What the flash kernels (forward, backward) must compute in a step:
    the attention layers at 32 heads of 64, t 8192."""
    return attention_flops(cfg, rows, cfg["input"]["seq_len"])


def flash_bytes(cfg: dict, rows: int) -> int:
    """Least HBM traffic of those kernels in bf16: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv — with
    keys and values at their 8 heads, not repeated to the 32."""
    h, kv, hd = _heads(cfg)
    layers = sum(mixer == "attention" for mixer, _ in kinds(cfg))
    forward, backward = 2 * h + 2 * kv, 4 * h + 4 * kv
    return layers * (forward + backward) * rows * cfg["input"]["seq_len"] * hd * 2
