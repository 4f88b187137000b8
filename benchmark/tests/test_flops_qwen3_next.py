"""benchmark/flops/qwen3_next.py against counts made by hand."""
import json
import os

from benchmark import harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load():
    with open(os.path.join(HERE, "configs", "qwen3-next-80b-a3b-l4.json")) as f:
        return harness.module("flops", "qwen3_next"), json.load(f)


def test_weights_per_token_by_hand():
    f, c = load()
    delta = 2048 * 12288 + 2048 * 64 + 4096 * 2048          # 33.69 M
    attn = 2048 * 9216 + 4096 * 2048                        # 27.26 M
    moe = 2048 * 512 + 3 * 2048 * 512 + 2048 + 10 * (32 / 512) * 3 * 2048 * 512
    want = 3 * delta + attn + 4 * moe + 2048 * 18992
    assert f.matmul_weights_per_token(c) == want
    assert 190e6 < want < 195e6


def test_step_is_23_tflop():
    f, c = load()
    t = 8192
    attn = 2 * t * 6 * t * 4096
    scan_tok = 2 * 128 * 128 * 128 // 64                    # S' = A S + B, a chunk of 64
    rule = 3 * 3 * 2 * t * 32 * (scan_tok + 4 * 128 * 128 + 2 * 64 * 128
                                 + 4 * 64 * 128 + 64 * 256)
    assert f.attention_flops(c, 2, t) == attn == f.flash_flops(c, 2)
    assert f.delta_rule_flops(c, 2, t) == rule
    assert f.step_flops(c, 2) == int(6 * f.matmul_weights_per_token(c) * 2 * t + attn + rule)
    assert 22e12 < f.step_flops(c, 2) < 24e12


def test_kernel_least_costs():
    f, c = load()
    t = 8192
    # bf16 q k v o forward, q k v o do dq dk dv backward, 16 heads of 256
    assert f.flash_bytes(c, 2) == 12 * 2 * t * 4096 * 2
    # the chunk rule with a chunk kept on the chip (PR 51; by hand in test_scoped_readers.py):
    # bound by its bytes on a v5e (197 TFLOP/s, 819 GB/s)
    assert f.gdn_flops(c, 2) == f.delta_rule_flops(c, 2, t)
    assert f.gdn_bytes(c, 2) / 819e9 > f.gdn_flops(c, 2) / 197e12
