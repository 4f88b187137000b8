"""A tiny `kimi-linear-48b-a3b-l5` for the CPU rehearsals and tests: the five
layers (KDA + dense, KDA, KDA, MLA, KDA, the last four with experts), 4 of 8
experts held from the third on, a latent head whose keys are wider than its
values, and sequences that are no multiple of the chunk of 64."""
from __future__ import annotations

import copy

from benchmark.tests import tiny


def kimi_linear(precision="float32", seq_len=80) -> dict:
    cfg = copy.deepcopy(tiny.config("kimi-linear-48b-a3b-l5"))
    linear = dict(cfg["linear_attn_config"], num_heads=4, head_dim=8)
    small = dict(
        hidden_size=32, vocab_size=48, linear_attn_config=linear,
        num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, intermediate_size=64, num_experts=4, num_experts_published=8,
        experts_first=2, moe_intermediate_size=16)
    cfg.update(small, num_experts_per_token=3, num_experts_per_tok=3)
    cfg["program"]["args"].update(small, num_experts_per_token=3, max_length=seq_len,
                                  capacity_factor=2.0, remat=None)
    cfg["program"]["precision"] = precision
    cfg["input"] = {"kind": "tokens", "seq_len": seq_len, "vocab": 48}
    return cfg
