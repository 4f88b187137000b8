"""A tiny `laguna-s-2.1-l5` for the CPU rehearsals and tests: the five layers
from published layer 0 on (global + dense, then sliding, sliding, sliding,
global over experts), 2 / 3 query heads over 1 key/value head of 16 held (1
of 2 head ranks of 4 / 6 over 2), a window of 8 over 32 tokens, 4 of 8
experts held from the third on beside the shared one; both rotary recipes as
published (the yarn one with its span cut to the tiny sequence's order, so
that its ramp lies inside the head's 4 pairs)."""
from __future__ import annotations

import copy

from benchmark.tests import tiny


def laguna(precision="float32", seq_len=32) -> dict:
    cfg = copy.deepcopy(tiny.config("laguna-s-2.1-l5"))
    rope = copy.deepcopy(cfg["rope_parameters"])
    rope["full_attention"].update(original_max_position_embeddings=16, factor=8,
                                  beta_fast=2.0, beta_slow=0.25, rope_theta=100)
    small = dict(
        hidden_size=32, vocab_size=48, num_hidden_layers=5, head_dim=16,
        num_attention_heads_per_layer=[2, 3, 3, 3] * 12, num_key_value_heads=1,
        sliding_window=8, rope_parameters=rope, intermediate_size=64,
        moe_intermediate_size=16, shared_expert_intermediate_size=16, num_experts=4,
        num_experts_published=8, experts_first=2, num_experts_per_tok=3)
    cfg.update(small)
    cfg["program"]["args"].update(small, max_length=seq_len, capacity_factor=2.0, remat=None)
    cfg["program"]["precision"] = precision
    cfg["input"] = {"kind": "tokens", "seq_len": seq_len, "vocab": 48}
    return cfg
