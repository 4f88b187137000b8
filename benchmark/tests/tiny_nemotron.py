"""A tiny `nemotron-3-nano-30b-a3b-l9` for the CPU rehearsals and tests: the
nine layers of the pattern, 4 of 8 experts held from the third on, chunks of
32 and sequences that are no multiple of them."""
from __future__ import annotations

import copy

from benchmark.tests import tiny


def nemotron_h(precision="float32", seq_len=80, pattern="MEMEMEM*E") -> dict:
    cfg = copy.deepcopy(tiny.config("nemotron-3-nano-30b-a3b-l9"))
    small = dict(
        hidden_size=32, vocab_size=48, hybrid_override_pattern=pattern,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
        chunk_size=32, num_experts=4, num_experts_published=8, experts_first=2,
        num_experts_per_tok=3, moe_intermediate_size=16,
        moe_shared_expert_intermediate_size=32)
    cfg.update(small, num_hidden_layers=len(pattern), n_routed_experts=4)
    cfg["program"]["args"].update(small, max_length=seq_len, capacity_factor=2.0,
                                  remat=None)
    cfg["program"]["precision"] = precision
    cfg["input"] = {"kind": "tokens", "seq_len": seq_len, "vocab": 48}
    return cfg
