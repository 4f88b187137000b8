"""benchmark/flops/kimi_linear.py against counts made by hand (ISSUE 33's
count) and against the parameter count of the net the program builds."""
import json
import os

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load():
    with open(os.path.join(HERE, "configs", "kimi-linear-48b-a3b-l5.json")) as f:
        return harness.module("flops", "kimi_linear"), json.load(f)


def test_parameters_by_hand():
    f, c = load()
    per = f.layer_parameters(c)
    # q k v 3 x 2304 x 4096, three convolutions 4 x 4096, two bottlenecks 2304 x 128 x 4096,
    # A_log, dt_bias, beta 2304 x 32, the head norm, o 4096 x 2304; + the pre-norm
    assert per["kda"] - 2304 == (3 * 2304 * 4096 + 3 * 4 * 4096 + 2 * (2304 * 128 + 128 * 4096)
                                 + 32 + 4096 + 2304 * 32 + 128 + 4096 * 2304) == 39_514_272
    # q 2304 x 32 x 192, [c | kr] 2304 x 576, the latent norm, [k_nope | v] 512 x 32 x 256, o
    assert per["mla"] - 2304 == (2304 * 6144 + 2304 * 576 + 512 + 512 * 8192
                                 + 4096 * 2304) == 29_114_880
    assert per["dense"] - 2304 == 3 * 2304 * 9216
    # router + bias, one shared expert, 8 experts of 3 x 2304 x 1024
    assert per["moe"] - 2304 == 2304 * 256 + 256 + 9 * 3 * 2304 * 1024 == 64_291_072
    assert per["kda"] + per["dense"] == 103_219_872                 # layer 1
    assert per["kda"] + per["moe"] == 103_809_952                   # layers 2, 3, 5
    assert per["mla"] + per["moe"] == 93_410_560                    # layer 4
    assert per["embedding"] + per["head"] == 94_371_840
    total = 103_219_872 + 3 * 103_809_952 + 93_410_560 + 94_371_840 + 2304
    assert f.parameters(c) == total == 602_434_432                  # 602.4 M
    assert round(16 * total / 1e9, 2) == 9.64                       # GB at 16 B a parameter
    # sixteen experts a rank (16 ranks a layer) would be 829 M = 13.3 GB: too much
    more = total + 4 * 8 * 3 * 2304 * 1024
    assert round(more / 1e6) == 829 and round(16 * more / 1e9, 1) == 13.3


def test_the_built_net_has_that_many_parameters():
    """`parameters` against the leaves of the net the program builds from
    the same file (shapes only: nothing of 602 M parameters is allocated)."""
    import jax

    from deeplearning4j_tpu import zoo

    f, c = load()
    args = {k: v for k, v in c["program"]["args"].items()}
    conf = getattr(zoo, c["program"]["zoo"])(**args).conf()
    from deeplearning4j_tpu.models import MultiLayerNetwork

    net = MultiLayerNetwork(conf)
    shapes = jax.eval_shape(lambda: net.init().params)
    count = sum(int(a.size) for a in jax.tree_util.tree_leaves(shapes))
    assert count == f.parameters(c) == 602_434_432
    ref = harness.module("reference", c["reference"])
    import math
    assert sum(math.prod(s) for s in ref.leaf_shapes(c).values()) == count


def test_weights_per_token_by_hand():
    f, c = load()
    kda = 3 * 2304 * 4096 + 2304 * 288 + 2 * 128 * 4096 + 4096 * 2304        # 39.46 M
    mla = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304                # 29.11 M
    moe = 2304 * 256 + 3 * 2304 * 1024 + 8 * (8 / 256) * 3 * 2304 * 1024
    want = 4 * kda + mla + 3 * 2304 * 9216 + 4 * moe + 2304 * 20480
    assert f.matmul_weights_per_token(c) == want
    assert 335e6 < want < 336e6


def test_step_is_39_tflop():
    f, c = load()
    t = 8192
    # keys 192, values 128 as published: Q K^T 2 t 192 + P V 2 t 128 a token and head forward,
    # x 3 with the backward, halved by the mask
    attn = 2 * t * 3 * t * 32 * (192 + 128)
    assert f.attention_flops(c, 2, t) == attn == f.flash_flops(c, 2)
    assert f.flash_flops(c, 2) < 2 * t * 3 * t * 32 * (256 + 128)      # not a padded 256
    tok = (4 * 64 * 128 + 64 * 256 + 2 * 128 * 128 * 2 + 4 * 128 * 128 + 2 * 64 * 128
           + 2 * 128 * 128 * 128 / 64)
    kda = int(3 * 4 * 2 * t * 32 * tok)
    assert f.kda_flops(c, 2) == kda
    assert f.step_flops(c, 2) == int(6 * f.matmul_weights_per_token(c) * 2 * t + attn + kda)
    assert 38.5e12 < f.step_flops(c, 2) < 39e12
    assert 4.1e12 < attn < 4.2e12 and 1.6e12 < kda < 1.7e12


def test_kernel_least_costs():
    f, c = load()
    t = 8192
    # bf16: forward q k (192) v o (128); backward q k dq dk (192) and v o do dv (128)
    assert f.flash_bytes(c, 2) == 2 * t * 32 * (6 * 192 + 6 * 128) * 2
    # float32 a token: q k v g (4096 each) and beta (32) in, o and the chunk's start state
    # (32 x 128 x 128 / 64 = 8192) out; backward the same in with do, dq dk dv dg dbeta out
    inputs = 4 * 4096 + 32
    forward = inputs + 4096 + 8192
    backward = inputs + 4096 + 8192 + inputs
    assert f.kda_bytes(c, 2) == 4 * 2 * t * (forward + backward) * 4
    # the core is bound by its bytes on a v5e (197 TFLOP/s, 819 GB/s)
    assert f.kda_bytes(c, 2) / 819e9 > 2 * f.kda_flops(c, 2) / 197e12


def test_configuration_file_keeps_the_published_widths():
    _, c = load()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r["source_url"] == c["source"])
    assert set(c["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c["published"][key] == value, key
        else:
            assert c[key] == value, key
    assert c["num_experts"] == 8 and c["num_experts_published"] == 256
    assert c["num_experts_per_tok"] == c["num_experts_per_token"] == 8
    assert "32 expert-parallel ranks a layer" in c["deployment"]
    args = c["program"]["args"]
    for key in ("hidden_size", "linear_attn_config", "num_attention_heads", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "first_k_dense_replace",
                "intermediate_size", "moe_intermediate_size", "num_experts_per_token",
                "num_shared_experts", "routed_scaling_factor", "moe_renormalize", "vocab_size",
                "num_hidden_layers", "num_experts", "rms_norm_eps"):
        assert args[key] == c[key], key
    assert args["capacity_factor"] * 16384 * 8 * 8 / 256 == 16384 * 8    # every assignment
