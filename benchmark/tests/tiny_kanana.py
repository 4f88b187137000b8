"""A tiny `kanana-2-30b-a3b-l5` for the CPU rehearsals and tests: three
layers of latent attention with rotary positions (dense first, then two with
experts), 4 of 8 experts held from the third on, two shared experts, a latent
head whose keys are wider than its values and whose rope part is four pairs."""
from __future__ import annotations

import copy

from benchmark.tests import tiny


def kanana(precision="float32", seq_len=80) -> dict:
    cfg = copy.deepcopy(tiny.config("kanana-2-30b-a3b-l5"))
    small = dict(
        hidden_size=32, vocab_size=48, num_hidden_layers=3, num_attention_heads=4,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
        intermediate_size=64, moe_intermediate_size=16, experts_first=2)
    cfg.update(small, n_routed_experts=4, n_routed_experts_published=8, num_experts=4,
               num_experts_published=8, num_experts_per_tok=3, qk_head_dim=16)
    cfg["program"]["args"].update(small, num_experts=4, num_experts_published=8,
                                  num_experts_per_token=3, max_length=seq_len,
                                  capacity_factor=2.0, remat=None)
    cfg["program"]["precision"] = precision
    cfg["input"] = {"kind": "tokens", "seq_len": seq_len, "vocab": 48}
    return cfg
