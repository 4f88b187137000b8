"""benchmark/flops/laguna.py against counts made by hand (ISSUE 47's table)
and against the parameter count of the net the program builds; the band's
count against a loop over the queries."""
import json
import math
import os

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load():
    with open(os.path.join(HERE, "configs", "laguna-s-2.1-l5.json")) as f:
        return harness.module("flops", "laguna"), json.load(f)


def test_parameters_by_hand():
    f, c = load()
    per = f.layer_parameters(c)
    # [q | k | v] 3072 x (24 + 4 + 4) x 128, the head gate 3072 x 24, o (24 x 128) x 3072
    assert per["full"] == 12_582_912 + 73_728 + 9_437_184 == 22_093_824
    # 36 query heads over the same 4 key/value heads
    assert per["sliding"] == 17_301_504 + 110_592 + 14_155_776 == 31_567_872
    assert per["dense"] == 3 * 3072 * 12288 == 113_246_208
    # the router over the 256 published, 8 experts of 3 x 3072 x 1024, the shared expert
    assert per["moe"] == 786_432 + 8 * 9_437_184 + 9_437_184 == 85_721_088
    assert per["embedding"] == per["head"] == 12544 * 3072 == 38_535_168
    norms = (2 * 5 + 1) * 3072
    total = (22_093_824 + 113_246_208 + 3 * (31_567_872 + 85_721_088)
             + 22_093_824 + 85_721_088 + norms + 2 * 38_535_168)
    assert f.parameters(c) == total == 672_125_952                  # 672.1 M
    assert round(16 * total / 1e9, 2) == 10.75                      # GB at 16 B a parameter
    # ISSUE 47's other rows: all heads held (no chip holds it with the buffer), 1 of 4 (the fallback)
    whole = dict(c, num_attention_heads_per_layer=c["published"]["num_attention_heads_per_layer"],
                 num_key_value_heads=8)
    assert f.parameters(whole) == 811_017_216 and round(16 * 811_017_216 / 1e9, 2) == 12.98
    quarter = dict(c, num_attention_heads_per_layer=[12, 18, 18, 18] * 12, num_key_value_heads=2)
    assert f.parameters(quarter) == 602_680_320 and round(16 * 602_680_320 / 1e9, 2) == 9.64


def test_the_built_net_has_that_many_parameters():
    """`parameters` against the leaves of the net the program builds from
    the same file (shapes only: nothing of 672 M parameters is allocated)."""
    import jax

    from deeplearning4j_tpu import zoo
    from deeplearning4j_tpu.models import MultiLayerNetwork

    f, c = load()
    args = {k: tuple(v) if isinstance(v, list) else v for k, v in c["program"]["args"].items()}
    net = MultiLayerNetwork(getattr(zoo, c["program"]["zoo"])(**args).conf())
    shapes = jax.eval_shape(lambda: net.init().params)
    count = sum(int(a.size) for a in jax.tree_util.tree_leaves(shapes))
    assert count == f.parameters(c) == 672_125_952
    ref = harness.module("reference", c["reference"])
    assert sum(math.prod(s) for s in ref.leaf_shapes(c).values()) == count
    assert f.layers(c) == ref.layers(c) == [
        (False, 24, "dense"), (True, 36, "moe"), (True, 36, "moe"), (True, 36, "moe"),
        (False, 24, "moe")]
    names = {jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(shapes)}
    assert sum("Wg'" in n for n in names) == 5 and sum("shared_Wd" in n for n in names) == 4
    # every leaf of the program is named by the reference's mapping, with its shape
    paths = ref.program_paths(c)
    assert len(paths) == len(names)
    for name, path in paths.items():
        leaf = shapes
        for k in path:
            leaf = leaf[k]
        assert tuple(leaf.shape) == ref.leaf_shapes(c)[name], name


@pytest.mark.parametrize("t,window", [(64, 8), (64, 1), (40, 64), (8192, 512), (100, 100)])
def test_the_band_is_a_loop_over_the_queries(t, window):
    f, _ = load()
    assert f.band_scores(t, window) == sum(min(i + 1, window) for i in range(t))


def test_weights_per_token_and_the_step_by_hand():
    f, c = load()
    t = 8192
    attn = {False: 22_093_824, True: 31_567_872}
    moe = 3072 * 256 + 3 * 3072 * 1024 + 10 * (8 / 256) * 3 * 3072 * 1024
    want = (2 * attn[False] + 3 * attn[True] + 3 * 3072 * 12288 + 4 * moe + 3072 * 12544)
    assert f.matmul_weights_per_token(c) == want
    tri, band = t * (t + 1) // 2, 512 * 513 // 2 + (t - 512) * 512
    full = 2 * 24 * tri * 12 * 128
    slide = 3 * 36 * band * 12 * 128
    assert f.window_flash_flops(c, 1) == slide and f.flash_flops(c, 1) == full + slide
    assert f.step_flops(c, 1) == int(6 * want * t + full + slide)
    assert 19.5e12 < f.step_flops(c, 1) < 20.5e12                    # ISSUE 47: ~20 TFLOP a step
    # forward, by part (ISSUE 47's reckoning): a global triangle 0.41, a band 0.075 TFLOP
    assert round(24 * tri * 4 * 128 / 1e12, 2) == 0.41
    assert round(36 * band * 4 * 128 / 1e12, 3) == 0.075
    assert round(36 * tri * 4 * 128 / 1e12, 3) == 0.619              # the same layer as a masked triangle
    assert 8.2 < tri / band < 8.3                                    # what the skip is worth
    assert round(0.969 * t * 512) == round(band, -3) or abs(band / (t * 512) - 0.969) < 1e-3


def test_kernel_least_costs():
    f, c = load()
    t = 8192
    # bf16: forward q o (heads) k v (4); backward q o do dq (heads) and k v dk dv (4)
    one = lambda h: t * 128 * 2 * ((2 * h + 2 * 4) + (4 * h + 4 * 4))  # noqa: E731
    assert f.window_flash_bytes(c, 1) == 3 * one(36)
    assert f.flash_bytes(c, 1) == 3 * one(36) + 2 * one(24)
    # a band of 512 keys is still bound by its operations on a v5e (197 TFLOP/s, 819 GB/s):
    # 3.42 ms of them against 1.84 ms of bytes, forward + backward, three layers ..
    ops, moved = f.window_flash_flops(c, 1) / 197e12, f.window_flash_bytes(c, 1) / 819e9
    assert 1.8 * moved < ops < 1.9 * moved and round(1e3 * ops, 2) == 3.42
    # .. as the whole triangles are, by far
    full_flops = f.flash_flops(c, 1) - f.window_flash_flops(c, 1)
    assert full_flops / 197e12 > 14 * 2 * one(24) / 819e9


def test_configuration_file_keeps_the_published_widths():
    _, c = load()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r["source_url"] == c["source"])
    assert row["name"] == "Laguna-S-2.1"
    assert set(c["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size",
                                 "num_attention_heads_per_layer", "num_key_value_heads"}
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c["published"][key] == value, key
        else:
            assert c[key] == value, key
    # no width is cut: the head, the window, the three feed-forward widths, the model's
    assert (c["head_dim"], c["sliding_window"], c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"], c["shared_expert_intermediate_size"],
            c["num_experts_per_tok"]) == (128, 512, 3072, 12288, 1024, 1024, 10)
    assert (c["num_experts"], c["num_experts_published"], c["experts_first"]) == (8, 256, 0)
    assert (c["num_hidden_layers"], c["layers_first"], c["vocab_size"]) == (5, 0, 12544)
    # one of two head ranks, in the published ratio a key/value head
    held, pub = c["num_attention_heads_per_layer"], c["published"]["num_attention_heads_per_layer"]
    assert [2 * h for h in held] == pub and c["num_key_value_heads"] * 2 == 8
    assert {h // c["num_key_value_heads"] for h in held} == {6, 9} == {h // 8 for h in pub}
    for phrase in ("each layer shared by 32 chips", "experts 32 ways", "heads 2 ways",
                   "8 ways over the vocabulary", "672 125 952 parameters"):
        assert phrase in c["deployment"], phrase
    assert {"router scores", "shared expert", "q/k norm", "gate", "window", "positions"} <= set(
        c["assumed"])
    assert any("other tensor-parallel rank's heads" in d for d in c["departures"])
    args = c["program"]["args"]
    for key in ("hidden_size", "head_dim", "num_key_value_heads", "layer_types",
                "num_attention_heads_per_layer", "sliding_window", "rope_parameters",
                "mlp_only_layers", "intermediate_size", "moe_intermediate_size",
                "shared_expert_intermediate_size", "num_experts_per_tok",
                "moe_routed_scaling_factor", "norm_topk_prob", "rms_norm_eps", "vocab_size",
                "num_hidden_layers", "num_experts", "num_experts_published", "experts_first",
                "layers_first"):
        assert args[key] == c[key], key
    assert args["capacity_factor"] * 8192 * 10 * 8 / 256 == 8192 * 10     # every assignment
    assert (args["remat"], args["max_length"], c["input"]["seq_len"]) == ("full", 8192, 8192)
