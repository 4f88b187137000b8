"""A tiny `mellum2-12b-a2.5b-l4` for the CPU rehearsals and tests: the four
layers from published layer 0 on (sliding, sliding, sliding, full, each over
routed experts), 4 query heads over 2 key/value heads of 16, a window of 8
over 32 tokens, ALL 8 experts held (2 a rank over the 4 ranks of
`expert_parallel`), top-2; both rotary recipes as published (the yarn one
with its span cut to the tiny sequence's order, so that its ramp lies inside
the head's 8 pairs)."""
from __future__ import annotations

import copy

from benchmark.tests import tiny

TRAIN_IDS_MESH = {"kind": "train_stream_ids_mesh", "per_chip_batch": 1, "distinct_batches": 3,
                  "check_steps": 3, "trace_seconds": 1, "attribution_seconds": 1}


def mellum2(precision="float32", seq_len=32) -> dict:
    cfg = copy.deepcopy(tiny.config("mellum2-12b-a2.5b-l4"))
    rope = copy.deepcopy(cfg["rope_parameters"])
    rope["full_attention"].update(original_max_position_embeddings=16, factor=8,
                                  beta_fast=2.0, beta_slow=0.25, rope_theta=100)
    rope["sliding_attention"].update(rope_theta=100)
    small = dict(
        hidden_size=32, vocab_size=48, num_hidden_layers=4, head_dim=16,
        num_attention_heads=4, num_key_value_heads=2, sliding_window=8, rope_parameters=rope,
        moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2)
    cfg.update(small)
    cfg["program"]["args"].update(small, max_length=seq_len, capacity_factor=4.0, remat=None)
    cfg["program"]["precision"] = precision
    cfg["input"] = {"kind": "tokens", "seq_len": seq_len, "vocab": 48}
    return cfg
