"""benchmark/flops/lfm2_moe.py against counts made by hand (ISSUE 40's count)
and against the parameter count of the net the program builds."""
import json
import math
import os

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load():
    with open(os.path.join(HERE, "configs", "lfm2-24b-a2b-l5.json")) as f:
        return harness.module("flops", "lfm2_moe"), json.load(f)


def test_parameters_by_hand():
    f, c = load()
    per = f.layer_parameters(c)
    # W_in 2048 x 6144, three taps a channel, W_out 2048 x 2048
    assert per["conv"] == 12_582_912 + 3 * 2048 + 4_194_304 == 16_783_360
    # [q | k | v] 2048 x (32 + 8 + 8) x 64, the two heads' norms, o 2048 x 2048
    assert per["attention"] == 6_291_456 + 2 * 64 + 4_194_304 == 10_485_888
    assert per["dense"] == 3 * 2048 * 11776 == 72_351_744
    # router + bias over the 64 published, 16 experts of 3 x 2048 x 1536, no shared expert
    assert per["moe"] == 131_072 + 64 + 16 * 9_437_184 == 151_126_080
    assert per["embedding"] == per["head"] == 16_777_216
    norms = (2 * 5 + 1) * 2048
    total = 4 * 16_783_360 + 10_485_888 + 72_351_744 + 4 * 151_126_080 + norms + 2 * 16_777_216
    assert norms == 22_528
    assert f.parameters(c) == total == 788_052_352                  # 788.1 M
    assert round(16 * total / 1e9, 2) == 12.61                      # GB at 16 B a parameter
    # the fallback of 8 experts a layer (not built: the step fits with 16)
    assert f.parameters(dict(c, num_experts=8)) == 486_062_464
    assert round(16 * 486_062_464 / 1e9, 2) == 7.78
    # whole, one expert layer is 604 M = 9.7 GB: no chip holds two
    whole = 131_072 + 64 + 64 * 9_437_184
    assert round(whole / 1e6) == 604 and round(16 * whole / 1e9, 1) == 9.7


def test_the_built_net_has_that_many_parameters():
    """`parameters` against the leaves of the net the program builds from
    the same file (shapes only: nothing of 788 M parameters is allocated)."""
    import jax

    from deeplearning4j_tpu import zoo
    from deeplearning4j_tpu.models import MultiLayerNetwork

    f, c = load()
    conf = getattr(zoo, c["program"]["zoo"])(**c["program"]["args"]).conf()
    net = MultiLayerNetwork(conf)
    shapes = jax.eval_shape(lambda: net.init().params)
    count = sum(int(a.size) for a in jax.tree_util.tree_leaves(shapes))
    assert count == f.parameters(c) == 788_052_352
    ref = harness.module("reference", c["reference"])
    assert sum(math.prod(s) for s in ref.leaf_shapes(c).values()) == count
    assert f.kinds(c) == ref.kinds(c) == [("conv", "dense"), ("attention", "moe"),
                                          ("conv", "moe"), ("conv", "moe"), ("conv", "moe")]
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    assert not any("shared" in jax.tree_util.keystr(path) for path, _ in leaves)


def test_weights_per_token_by_hand():
    f, c = load()
    conv = 2048 * 6144 + 2048 * 2048                                  # 16.78 M
    attn = 2048 * 48 * 64 + 2048 * 2048                               # 10.49 M
    # the router over 64, 4 chosen of which 16 / 64 live here
    moe = 2048 * 64 + 4 * (16 / 64) * 3 * 2048 * 1536
    want = 4 * conv + attn + 3 * 2048 * 11776 + 4 * moe + 2048 * 8192
    assert f.matmul_weights_per_token(c) == want
    assert 204e6 < want < 206e6


def test_step_is_22_tflop():
    f, c = load()
    t = 8192
    # Q K^T 2 t 64 + P V 2 t 64 a token and head forward, x 3 with the backward, halved by the
    # mask; 32 query heads (the 8 key/value heads add no product); one layer
    attn = 2 * t * 3 * t * 32 * (64 + 64)
    assert f.attention_flops(c, 2, t) == attn == f.flash_flops(c, 2)
    assert f.step_flops(c, 2) == int(6 * f.matmul_weights_per_token(c) * 2 * t + attn)
    assert 21.5e12 < f.step_flops(c, 2) < 22.1e12
    assert 1.6e12 < attn < 1.7e12                                     # 7.6 % of the step
    # ISSUE 40's reckoning, by part (6 x weights x 16 384 tokens)
    six = 6 * 2 * t
    assert round(six * (2048 * 6144 + 2048 * 2048) / 1e12, 2) == 1.65   # a conv mixer
    assert round(six * 3 * 2048 * 11776 / 1e12, 1) == 7.1               # the dense feed-forward
    assert round(six * 4 * 3 * 2048 * 1536 / 1e12, 1) == 3.7            # the 16 held, 4 layers
    assert round(six * 4 * 4 * 3 * 2048 * 1536 / 1e12, 1) == 14.8       # over the whole buffer


def test_kernel_least_costs():
    f, c = load()
    t = 8192
    # bf16: forward q o (32 heads) k v (8); backward q o do dq (32) and k v dk dv (8)
    assert f.flash_bytes(c, 2) == 2 * t * 64 * 2 * ((2 * 32 + 2 * 8) + (4 * 32 + 4 * 8))
    # attention at t 8192 is bound by its operations on a v5e (197 TFLOP/s, 819 GB/s)
    assert f.flash_flops(c, 2) / 197e12 > 10 * f.flash_bytes(c, 2) / 819e9


def test_configuration_file_keeps_the_published_widths():
    _, c = load()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r["source_url"] == c["source"])
    assert row["name"] == "LFM2-24B-A2B"
    assert set(c["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c["published"][key] == value, key
        else:
            assert c[key] == value, key
    assert (c["num_experts"], c["num_experts_published"], c["experts_first"]) == (16, 64, 0)
    assert (c["num_hidden_layers"], c["layers_first"], c["vocab_size"]) == (5, 1, 8192)
    assert "4 expert-parallel ranks a layer" in c["deployment"]
    assert "788 052 352 parameters" in c["deployment"]
    args = c["program"]["args"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads", "layer_types",
                "conv_L_cache", "rope_parameters", "num_dense_layers", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok", "routed_scaling_factor",
                "norm_topk_prob", "norm_eps", "vocab_size", "num_hidden_layers", "num_experts",
                "num_experts_published", "experts_first", "layers_first"):
        assert args[key] == c[key], key
    assert args["capacity_factor"] * 16384 * 4 * 16 / 64 == 16384 * 4    # every assignment
    assert (args["remat"], args["max_length"], c["input"]["seq_len"]) == ("full", 8192, 8192)
