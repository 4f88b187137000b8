"""Operations and least bytes of one `laguna` training step
(`laguna-s-2.1-l5`), from its shapes alone: matrix multiplications (2 FLOPs a
weight and token forward, 6 with the backward) and grouped-query attention at
its published head width — the whole causal triangle in a `full_attention`
layer, the BAND of `sliding_window` keys in a `sliding_attention` one,
whatever blocks a kernel visits for it. Nothing recomputed is counted; the
routed experts count the EXPECTED assignments of the experts held (tokens x
top-k x held / published), not the buffer's padding. Norms, the rotation, the
head gates' sigmoid, the router's softmax and the sort move bytes, they are
not the FLOPs.
"""
from __future__ import annotations


def layers(cfg: dict):
    """(is the layer windowed, query heads held, "dense" | "moe") of each
    layer built: the published layers `layers_first` .. on."""
    first = cfg.get("layers_first", 0)
    return [(cfg["layer_types"][i] == "sliding_attention",
             cfg["num_attention_heads_per_layer"][i],
             "dense" if i in cfg["mlp_only_layers"] else "moe")
            for i in range(first, first + cfg["num_hidden_layers"])]


def _attention_weights(cfg, h: int) -> int:
    """[q | k | v], the head gate and o of a layer of `h` query heads."""
    d, hd, kv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    return d * (h + 2 * kv) * hd + d * h + h * hd * d


def layer_parameters(cfg: dict) -> dict:
    """Parameters of one sub-layer of each kind as held here, WITHOUT its
    pre-norm, the embedding and the head. "full" / "sliding": an attention
    layer at the first such layer's head count."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    published = cfg["num_experts_published"]
    heads = {windowed: h for windowed, h, _ in reversed(layers(cfg))}
    return {
        "full": _attention_weights(cfg, heads[False]),
        "sliding": _attention_weights(cfg, heads[True]),
        "dense": 3 * d * cfg["intermediate_size"],
        "moe": (d * published + cfg["num_experts"] * 3 * d * f
                + 3 * d * cfg["shared_expert_intermediate_size"]),
        "norm": d,
        "embedding": cfg["vocab_size"] * d, "head": d * cfg["vocab_size"],
    }


def parameters(cfg: dict) -> int:
    per = layer_parameters(cfg)
    return (sum(_attention_weights(cfg, h) + per[ffn] + 2 * per["norm"]
                for _, h, ffn in layers(cfg))
            + per["embedding"] + per["head"] + per["norm"])


def matmul_weights_per_token(cfg: dict) -> float:
    """Weights every token is multiplied with, forward, over the layers."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["num_experts"] / cfg["num_experts_published"]
    ffn = {"dense": 3 * d * cfg["intermediate_size"],
           "moe": d * cfg["num_experts_published"]
           + 3 * d * cfg["shared_expert_intermediate_size"]
           + cfg["num_experts_per_tok"] * held * 3 * d * f}
    return (sum(_attention_weights(cfg, h) + ffn[kind] for _, h, kind in layers(cfg))
            + d * cfg["vocab_size"])                  # the embedding gather is free


def band_scores(t: int, window: int) -> int:
    """(query, key) pairs with 0 <= i - j < window over t tokens: the whole
    triangle t (t + 1) / 2 where the window reaches back over everything."""
    w = min(window, t)
    return w * (w + 1) // 2 + (t - w) * w


def _attention_flops(cfg, rows: int, t: int, windowed: bool) -> int:
    """Forward + backward of the layers of one kind: per score Q K^T and P V
    are 2 d FLOPs each forward, twice that backward: 12 d a (query, key) pair
    and head."""
    heads = sum(h for w, h, _ in layers(cfg) if w == windowed)
    pairs = band_scores(t, cfg["sliding_window"]) if windowed else band_scores(t, t)
    return rows * heads * pairs * 12 * cfg["head_dim"]


def _attention_bytes(cfg, rows: int, t: int, windowed: bool) -> int:
    """Least HBM traffic of the flash kernels of the layers of one kind in
    bf16: forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv — with keys and values at their own heads, not
    repeated to the query heads."""
    kv = cfg["num_key_value_heads"]
    total = sum((2 * h + 2 * kv) + (4 * h + 4 * kv) for w, h, _ in layers(cfg) if w == windowed)
    return total * rows * t * cfg["head_dim"] * 2


def window_flash_flops(cfg: dict, rows: int) -> int:
    """What the BANDS of the sliding layers cost, forward + backward, counted
    from the shapes and the window alone."""
    return _attention_flops(cfg, rows, cfg["input"]["seq_len"], True)


def window_flash_bytes(cfg: dict, rows: int) -> int:
    return _attention_bytes(cfg, rows, cfg["input"]["seq_len"], True)


def flash_flops(cfg: dict, rows: int) -> int:
    """What ALL the flash kernels must compute in a step: the global layers'
    triangles + the sliding layers' bands."""
    t = cfg["input"]["seq_len"]
    return _attention_flops(cfg, rows, t, False) + _attention_flops(cfg, rows, t, True)


def flash_bytes(cfg: dict, rows: int) -> int:
    t = cfg["input"]["seq_len"]
    return _attention_bytes(cfg, rows, t, False) + _attention_bytes(cfg, rows, t, True)


def step_flops(cfg: dict, rows: int) -> int:
    """One optimizer step on `rows` sequences of the configured length."""
    t = cfg["input"]["seq_len"]
    return int(6 * matmul_weights_per_token(cfg) * rows * t + flash_flops(cfg, rows))
