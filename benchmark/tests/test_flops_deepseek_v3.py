"""benchmark/flops/deepseek_v3.py against counts made by hand (ISSUE 38's
count) and against the parameter count of the net the program builds."""
import json
import math
import os

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load():
    with open(os.path.join(HERE, "configs", "kanana-2-30b-a3b-l5.json")) as f:
        return harness.module("flops", "deepseek_v3"), json.load(f)


def test_parameters_by_hand():
    f, c = load()
    per = f.layer_parameters(c)
    # q 2048 x 32 x 192, [c | kr] 2048 x 576, the latent norm, [k_nope | v] 512 x 32 x 256,
    # o 4096 x 2048; + the pre-norm
    assert per["mla"] - 2048 == (12_582_912 + 1_179_648 + 512 + 4_194_304
                                 + 8_388_608) == 26_345_984
    assert per["dense"] - 2048 == 3 * 2048 * 6144 == 37_748_736
    # router + bias, two shared experts as one swiglu of 1536, 16 experts of 3 x 2048 x 768
    assert per["moe"] - 2048 == 262_144 + 128 + 9_437_184 + 16 * 4_718_592 == 85_196_928
    assert per["mla"] + per["dense"] == 64_098_816                  # layer 1
    assert per["mla"] + per["moe"] == 111_547_008                   # layers 2..5
    assert per["embedding"] == per["head"] == 32_833_536
    total = 64_098_816 + 4 * 111_547_008 + 2 * 32_833_536 + 2048
    assert f.parameters(c) == total == 575_955_968                  # 576.0 M
    assert round(16 * total / 1e9, 2) == 9.22                       # GB at 16 B a parameter
    # a sixth layer would be 687.5 M = 11.0 GB; whole, one expert layer is 640 M = 10.2 GB
    assert round((total + 111_547_008) / 1e6, 1) == 687.5
    whole = per["mla"] + per["moe"] + 112 * 4_718_592
    assert round(whole / 1e6) == 640 and round(16 * whole / 1e9, 1) == 10.2


def test_the_built_net_has_that_many_parameters():
    """`parameters` against the leaves of the net the program builds from
    the same file (shapes only: nothing of 576 M parameters is allocated)."""
    import jax

    from deeplearning4j_tpu import zoo
    from deeplearning4j_tpu.models import MultiLayerNetwork

    f, c = load()
    conf = getattr(zoo, c["program"]["zoo"])(**c["program"]["args"]).conf()
    net = MultiLayerNetwork(conf)
    shapes = jax.eval_shape(lambda: net.init().params)
    count = sum(int(a.size) for a in jax.tree_util.tree_leaves(shapes))
    assert count == f.parameters(c) == 575_955_968
    ref = harness.module("reference", c["reference"])
    assert sum(math.prod(s) for s in ref.leaf_shapes(c).values()) == count
    assert f.kinds(c) == ref.kinds(c) == ["dense", "moe", "moe", "moe", "moe"]


def test_weights_per_token_by_hand():
    f, c = load()
    mla = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048                # 26.35 M
    # router over 128, the shared 1536, 6 chosen of which 16 / 128 live here
    moe = 2048 * 128 + 3 * 2048 * 1536 + 6 * (16 / 128) * 3 * 2048 * 768
    want = 5 * mla + 3 * 2048 * 6144 + 4 * moe + 2048 * 16032
    assert f.matmul_weights_per_token(c) == want
    assert 255e6 < want < 256e6


def test_step_is_46_tflop():
    f, c = load()
    t = 8192
    # keys 192, values 128 as published: Q K^T 2 t 192 + P V 2 t 128 a token and head forward,
    # x 3 with the backward, halved by the mask; five layers
    attn = 5 * 2 * t * 3 * t * 32 * (192 + 128)
    assert f.attention_flops(c, 2, t) == attn == f.flash_flops(c, 2)
    assert f.flash_flops(c, 2) < 5 * 2 * t * 3 * t * 32 * (256 + 128)   # not a padded 256
    assert f.step_flops(c, 2) == int(6 * f.matmul_weights_per_token(c) * 2 * t + attn)
    assert 45.5e12 < f.step_flops(c, 2) < 46.5e12
    assert 20.5e12 < attn < 20.7e12                                   # 45 % of the step


def test_kernel_least_costs():
    f, c = load()
    t = 8192
    # bf16: forward q k (192) v o (128); backward q k dq dk (192) and v o do dv (128); 5 layers
    assert f.flash_bytes(c, 2) == 5 * 2 * t * 32 * (6 * 192 + 6 * 128) * 2
    # attention at t 8192 is bound by its operations on a v5e (197 TFLOP/s, 819 GB/s)
    assert f.flash_flops(c, 2) / 197e12 > 5 * f.flash_bytes(c, 2) / 819e9


def test_configuration_file_keeps_the_published_widths():
    _, c = load()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r["source_url"] == c["source"])
    assert set(c["reduced"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c["published"][key] == value, key
        else:
            assert c[key] == value, key
    assert c["n_routed_experts"] == c["num_experts"] == 16
    assert c["n_routed_experts_published"] == c["num_experts_published"] == 128
    assert "8 expert-parallel ranks a layer" in c["deployment"]
    assert "575 955 968 parameters" in c["deployment"]
    args = c["program"]["args"]
    for key, published in (
            ("hidden_size", "hidden_size"), ("num_attention_heads", "num_attention_heads"),
            ("kv_lora_rank", "kv_lora_rank"), ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("qk_rope_head_dim", "qk_rope_head_dim"), ("v_head_dim", "v_head_dim"),
            ("rope_theta", "rope_theta"), ("rope_interleave", "rope_interleave"),
            ("first_k_dense_replace", "first_k_dense_replace"),
            ("intermediate_size", "intermediate_size"),
            ("moe_intermediate_size", "moe_intermediate_size"),
            ("num_experts_per_token", "num_experts_per_tok"),
            ("num_shared_experts", "n_shared_experts"),
            ("routed_scaling_factor", "routed_scaling_factor"),
            ("moe_renormalize", "norm_topk_prob"), ("vocab_size", "vocab_size"),
            ("num_hidden_layers", "num_hidden_layers"), ("num_experts", "n_routed_experts"),
            ("num_experts_published", "n_routed_experts_published"),
            ("rms_norm_eps", "rms_norm_eps")):
        assert args[key] == c[published], key
    assert args["mla_use_nope"] is False and args["linear_attn_config"] == {"kda_layers": []}
    assert args["capacity_factor"] * 16384 * 6 * 16 / 128 == 16384 * 6    # every assignment
