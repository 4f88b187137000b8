"""A tiny `ouro-2.6b-l6` for the CPU rehearsals and tests: two layers looped
four times over one set of weights, four heads of four rotary pairs (as many
key/value heads as query heads), the exit gate and the loss over all four
passes."""
from __future__ import annotations

import copy

from benchmark.tests import tiny, tiny_ids

TRAIN_IDS = tiny_ids.TRAIN_IDS


def ouro(precision="float32", seq_len=80, steps=4) -> dict:
    cfg = copy.deepcopy(tiny.config("ouro-2.6b-l6"))
    small = dict(
        hidden_size=32, vocab_size=48, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, head_dim=8, intermediate_size=64, total_ut_steps=steps)
    cfg.update(small)
    cfg["program"]["args"].update(small, max_length=seq_len, remat=None)
    cfg["program"]["precision"] = precision
    cfg["input"] = {"kind": "tokens", "seq_len": seq_len, "vocab": 48}
    return cfg
