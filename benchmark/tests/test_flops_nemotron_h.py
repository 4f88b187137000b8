"""benchmark/flops/nemotron_h.py against counts made by hand (the table under
ISSUE 31's Motivation)."""
import json
import os

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load():
    with open(os.path.join(HERE, "configs", "nemotron-3-nano-30b-a3b-l9.json")) as f:
        return harness.module("flops", "nemotron_h"), json.load(f)


def test_parameters_by_hand():
    f, c = load()
    per = f.layer_parameters(c)
    # in_proj 2688 x 10304, conv 6144 x 4 + 6144, A_log D dt_bias, gated norm, out_proj, pre-norm
    assert per["M"] == (2688 * (2 * 4096 + 2 * 8 * 128 + 64) + 6144 * 4 + 6144 + 3 * 64 + 4096
                        + 4096 * 2688 + 2688) == 38_744_896
    # q 2688 x 4096, k and v 2688 x 256 each, o 4096 x 2688, pre-norm
    assert per["*"] == 2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688 + 2688 == 23_399_040
    # router + bias, shared 2 x 2688 x 3712, 8 experts of 2 x 2688 x 1856, pre-norm
    assert per["E"] == (2688 * 128 + 128 + 2 * 2688 * 3712 + 8 * 2 * 2688 * 1856 + 2688
                        ) == 100_125_440
    assert per["embedding"] == per["head"] == 16384 * 2688
    total = 4 * per["M"] + per["*"] + 4 * per["E"] + 2 * 16384 * 2688 + 2688
    assert f.parameters(c) == total == 666_963_456                 # 667.0 M
    assert round(16 * total / 1e9, 2) == 10.67                     # GB at 16 B a parameter
    # the whole layer: 128 experts are 1.30 B parameters = 20.8 GB, no chip holds one
    whole = per["E"] + 120 * 2 * 2688 * 1856
    assert round(whole / 1e9, 2) == 1.30 and round(16 * whole / 1e9, 1) == 20.8
    # eight experts more (8 ranks a layer) would not fit
    assert 16 * (total + 4 * 8 * 2 * 2688 * 1856) > 15.7e9


def test_weights_per_token_by_hand():
    f, c = load()
    mamba = 2688 * 10304 + 4096 * 2688                      # 38.71 M
    attn = 2688 * 4608 + 4096 * 2688                        # 23.40 M
    moe = 2688 * 128 + 2 * 2688 * 3712 + 6 * (8 / 128) * 2 * 2688 * 1856
    want = 4 * mamba + attn + 4 * moe + 2688 * 16384
    assert f.matmul_weights_per_token(c) == want
    assert 318e6 < want < 319e6
    assert 154e6 < 4 * mamba < 156e6                        # the mixers' share of it


def test_step_is_35_tflop():
    f, c = load()
    t = 8192
    attn = 2 * t * 6 * t * 4096
    # per token and head: C B^T a group / 8 heads, scores x X, the chunk's state and
    # the read of the carried one, the scan's multiply-add a chunk of 128
    ssd_tok = 2 * 128 * 128 / 8 + 2 * 128 * 64 + 4 * 64 * 128 + 2 * 64 * 128 / 128
    ssd = int(3 * 4 * 2 * t * 64 * ssd_tok)
    assert f.attention_flops(c, 2, t) == attn == f.flash_flops(c, 2)
    assert f.ssd_flops(c, 2) == ssd
    assert f.step_flops(c, 2) == int(6 * f.matmul_weights_per_token(c) * 2 * t + attn + ssd)
    assert 35e12 < f.step_flops(c, 2) < 35.5e12
    assert 3.2e12 < attn < 3.4e12 and 0.6e12 < ssd < 0.7e12


def test_kernel_least_costs():
    f, c = load()
    t = 8192
    # bf16 q k v o forward, q k v o do dq dk dv backward, 32 heads of 128
    assert f.flash_bytes(c, 2) == 12 * 2 * t * 4096 * 2
    # float32 a token: forward x B C dt in, y and the chunk's start state out
    # (64 x 64 x 128 / 128 = 4096); backward the same in with dy, dx dB dC ddt out
    forward = (4096 + 2048 + 64) + 4096 + 4096
    backward = (4096 + 2048 + 64) + 4096 + 4096 + (4096 + 2048 + 64)
    assert f.ssd_bytes(c, 2) == 4 * 2 * t * (forward + backward) * 4
    # the core is bound by its bytes on a v5e (197 TFLOP/s, 819 GB/s)
    assert f.ssd_bytes(c, 2) / 819e9 > 3 * f.ssd_flops(c, 2) / 197e12


def test_configuration_file_keeps_the_published_widths():
    _, c = load()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r["source_url"] == c["source"])
    assert set(c["reduced"]) == {"num_hidden_layers", "hybrid_override_pattern",
                                 "n_routed_experts", "vocab_size"}
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c["published"][key] == value, key
        else:
            assert c[key] == value, key
    assert c["n_routed_experts"] == c["num_experts"] == 8 and c["num_experts_published"] == 128
    assert c["hybrid_override_pattern"] in c["published"]["hybrid_override_pattern"]
    assert len(c["hybrid_override_pattern"]) == c["num_hidden_layers"] == 9
    args = c["program"]["args"]
    for key in ("hidden_size", "head_dim", "mamba_num_heads", "mamba_head_dim", "n_groups",
                "ssm_state_size", "conv_kernel", "chunk_size", "num_attention_heads",
                "num_key_value_heads", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size", "num_experts_per_tok",
                "routed_scaling_factor", "vocab_size", "hybrid_override_pattern"):
        assert args[key] == c[key], key
