"""Tiny configurations for the CPU rehearsals of `train_stream_ids` cells."""
from __future__ import annotations

import copy

from benchmark.tests import tiny

TRAIN_IDS = {"kind": "train_stream_ids", "per_chip_batch": 2, "distinct_batches": 3,
             "check_steps": 3, "trace_seconds": 1, "attribution_seconds": 1}


def qwen3_next(precision="float32", seq_len=80) -> dict:
    """One period (delta, delta, delta, attention), 4 of 8 experts held,
    sequences that are no multiple of the chunk of 64."""
    cfg = copy.deepcopy(tiny.config("qwen3-next-80b-a3b-l4"))
    small = dict(
        hidden_size=32, vocab_size=48, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=8, num_experts=4,
        num_experts_published=8, experts_first=2, num_experts_per_tok=3,
        moe_intermediate_size=16, shared_expert_intermediate_size=16)
    cfg.update(small)
    cfg["program"]["args"].update(small, max_length=seq_len, capacity_factor=2.0,
                                  remat=None)
    cfg["program"]["precision"] = precision
    cfg["input"] = {"kind": "tokens", "seq_len": seq_len, "vocab": 48}
    return cfg
