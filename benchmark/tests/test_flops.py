"""Each benchmark/flops function against a count made by hand."""
import json
import os

from benchmark import harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_gpt2_weights_and_step():
    g, c = harness.module("flops", "gpt2"), cfg("gpt2-small")
    # per layer: qkv 768x2304, out 768x768, ffn 2 x 768x3072 = 7,077,888
    assert g.matmul_weights(c) == 12 * 7_077_888 + 768 * 50257 == 123_532_032
    # attention, one sequence: 12 layers x 1024 tokens x 6 x 1024 x 768
    assert g.attention_flops(c, 1, 1024) == 12 * 1024 * 6 * 1024 * 768
    one = 6 * 123_532_032 * 1024 + 12 * 1024 * 6 * 1024 * 768
    assert g.step_flops(c, 1) == one and g.step_flops(c, 8) == 8 * one
    assert g.flash_flops(c, 8) == 8 * 12 * 1024 * 6 * 1024 * 768
    # bf16 q k v o fwd (4) + q k v o do dq dk dv bwd (8), [8, 1024, 768] each
    assert g.flash_bytes(c, 8) == 12 * 12 * 8 * 1024 * 768 * 2


def test_gpt2_attention_is_half_of_the_profile_scripts():
    """profile_transformer.transformer_step_flops counts 2 t d multiply-adds
    per token for QK^T; there are t d. The benchmark's count is half."""
    g, c = harness.module("flops", "gpt2"), cfg("gpt2-small")
    theirs = 12 * 8 * 1024 * (4 * 2 * 1024 * 768) * 3 // 2
    assert 2 * g.attention_flops(c, 8, 1024) == theirs


def test_resnet50_by_hand():
    r, c = harness.module("flops", "resnet50"), cfg("resnet50")
    macs = dict(r.conv_macs(c))
    assert macs["stem"] == 112 * 112 * 49 * 3 * 64
    assert macs["s2.0.a"] == 56 * 56 * 64 * 64
    assert macs["s2.0.b"] == 56 * 56 * 9 * 64 * 64
    assert macs["s2.0.sc"] == 56 * 56 * 64 * 256
    assert macs["s3.0.a"] == 28 * 28 * 256 * 128      # the stride is on `a`
    assert macs["s5.2.c"] == 7 * 7 * 512 * 2048
    assert macs["fc"] == 2048 * 1000
    assert len(macs) == 1 + 16 * 3 + 4 + 1
    total = sum(macs.values())
    assert 3.8e9 < total < 3.9e9                      # the known ~3.86 GMACs
    assert r.forward_flops(c, 2) == 4 * total
    assert r.step_flops(c, 1) == 2 * (3 * total - macs["stem"])
