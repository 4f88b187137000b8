"""Operations and least bytes of one `mellum` training step
(`mellum2-12b-a2.5b-l4`), from its shapes alone: matrix multiplications (2
FLOPs a weight and token forward, 6 with the backward) and grouped-query
attention at its published head width — the whole causal triangle in a
`full_attention` layer, the BAND of `sliding_window` keys in a
`sliding_attention` one, whatever blocks a kernel visits for it. The published
work, counted ONCE for the whole step over however many chips share it: all
64 experts are held, so a token is multiplied with its whole top-8 and nothing
of the exchange is counted (rows in flight are bytes on the interconnect, not
FLOPs: `exchange_bytes`). Nothing recomputed is counted, nor the pair buffers'
padding. Norms, the rotation, the router's softmax and the sorts move bytes,
they are not the FLOPs.
"""
from __future__ import annotations


def layers(cfg: dict):
    """Is each layer built windowed: the published layers `layers_first` .. on."""
    first = cfg.get("layers_first", 0)
    return [cfg["layer_types"][i] == "sliding_attention"
            for i in range(first, first + cfg["num_hidden_layers"])]


def layer_parameters(cfg: dict) -> dict:
    """Parameters of one sub-layer of each kind WITHOUT its pre-norm, and of
    the embedding and the head."""
    d, hd, f = cfg["hidden_size"], cfg["head_dim"], cfg["moe_intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {
        "attention": d * (h + 2 * kv) * hd + h * hd * d,
        "router": d * cfg["num_experts"],
        "expert": 3 * d * f,
        "norm": d,
        "embedding": cfg["vocab_size"] * d, "head": d * cfg["vocab_size"],
    }


def parameters(cfg: dict) -> int:
    """The stage whole: every expert of every layer."""
    per = layer_parameters(cfg)
    layer = (per["attention"] + per["router"] + cfg["num_experts"] * per["expert"]
             + 2 * per["norm"])
    return cfg["num_hidden_layers"] * layer + per["embedding"] + per["head"] + per["norm"]


def parameters_per_chip(cfg: dict) -> int:
    """What one of the `expert_parallel` chips holds: its experts, and
    everything else whole."""
    per = layer_parameters(cfg)
    spread = cfg["num_hidden_layers"] * cfg["num_experts"] * per["expert"]
    return parameters(cfg) - spread + spread // cfg["expert_parallel"]


def matmul_weights_per_token(cfg: dict) -> int:
    """Weights every token is multiplied with, forward, over the layers."""
    per = layer_parameters(cfg)
    layer = per["attention"] + per["router"] + cfg["num_experts_per_tok"] * per["expert"]
    return cfg["num_hidden_layers"] * layer + per["head"]      # the embedding gather is free


def band_scores(t: int, window: int) -> int:
    """(query, key) pairs with 0 <= i - j < window over t tokens: the whole
    triangle t (t + 1) / 2 where the window reaches back over everything."""
    w = min(window, t)
    return w * (w + 1) // 2 + (t - w) * w


def _attention_flops(cfg, rows: int, t: int, windowed: bool) -> int:
    """Forward + backward of the layers of one kind: per score Q K^T and P V
    are 2 d FLOPs each forward, twice that backward: 12 d a (query, key) pair
    and head."""
    heads = cfg["num_attention_heads"] * sum(w == windowed for w in layers(cfg))
    pairs = band_scores(t, cfg["sliding_window"]) if windowed else band_scores(t, t)
    return rows * heads * pairs * 12 * cfg["head_dim"]


def _attention_bytes(cfg, rows: int, t: int, windowed: bool) -> int:
    """Least HBM traffic of the flash kernels of the layers of one kind in
    bf16: forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv — with keys and values at their own heads, not
    repeated to the query heads."""
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n = sum(w == windowed for w in layers(cfg))
    return n * ((2 * h + 2 * kv) + (4 * h + 4 * kv)) * rows * t * cfg["head_dim"] * 2


def window_flash_flops(cfg: dict, rows: int) -> int:
    """What the BANDS of the sliding layers cost, forward + backward, counted
    from the shapes and the window alone."""
    return _attention_flops(cfg, rows, cfg["input"]["seq_len"], True)


def window_flash_bytes(cfg: dict, rows: int) -> int:
    return _attention_bytes(cfg, rows, cfg["input"]["seq_len"], True)


def flash_flops(cfg: dict, rows: int) -> int:
    """What ALL the flash kernels must compute in a step: the full layers'
    triangles + the sliding layers' bands."""
    t = cfg["input"]["seq_len"]
    return _attention_flops(cfg, rows, t, False) + _attention_flops(cfg, rows, t, True)


def flash_bytes(cfg: dict, rows: int) -> int:
    t = cfg["input"]["seq_len"]
    return _attention_bytes(cfg, rows, t, False) + _attention_bytes(cfg, rows, t, True)


def step_flops(cfg: dict, rows: int) -> int:
    """One optimizer step on `rows` sequences of the configured length, over
    all the chips that share it."""
    t = cfg["input"]["seq_len"]
    return int(6 * matmul_weights_per_token(cfg) * rows * t + flash_flops(cfg, rows))
