"""A tiny `lfm2-24b-a2b-l5` for the CPU rehearsals and tests: four layers
from published layer 1 on (conv + dense, then attention, conv, conv over
experts), 4 of 8 experts held from the third on, no shared expert, grouped
key/value heads (2 under 4) of four rotary pairs."""
from __future__ import annotations

import copy

from benchmark.tests import tiny


def lfm2(precision="float32", seq_len=80) -> dict:
    cfg = copy.deepcopy(tiny.config("lfm2-24b-a2b-l5"))
    small = dict(
        hidden_size=32, vocab_size=48, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=64, moe_intermediate_size=16,
        num_experts=4, num_experts_published=8, experts_first=2, num_experts_per_tok=3)
    cfg.update(small)
    cfg["program"]["args"].update(small, max_length=seq_len, capacity_factor=2.0, remat=None)
    cfg["program"]["precision"] = precision
    cfg["input"] = {"kind": "tokens", "seq_len": seq_len, "vocab": 48}
    return cfg
