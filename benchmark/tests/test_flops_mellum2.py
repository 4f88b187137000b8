"""benchmark/flops/mellum2.py against counts made by hand (ISSUE 52's
reckoning) and against the parameter count of the net the program builds; the
configuration file against the catalog's row."""
import json
import math
import os

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load():
    with open(os.path.join(HERE, "configs", "mellum2-12b-a2.5b-l4.json")) as f:
        return harness.module("flops", "mellum2"), json.load(f)


def test_parameters_by_hand():
    f, c = load()
    per = f.layer_parameters(c)
    # q 2304 x 4096, k and v 2304 x 512 each, o 4096 x 2304
    assert per["attention"] == 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304 == 21_233_664
    assert per["router"] == 2304 * 64 and per["expert"] == 3 * 2304 * 896 == 6_193_152
    # a layer outside its experts: attention, two norms, the router
    assert per["attention"] + 2 * per["norm"] + per["router"] == 21_385_728
    assert 64 * per["expert"] == 396_361_728
    assert per["embedding"] + per["head"] == 2 * 24576 * 2304 == 113_246_208
    assert f.parameters(c) == 1_784_238_336                          # one period whole
    assert round(16 * f.parameters(c) / 1e9, 1) == 28.5              # GB at 16 B: no chip holds it
    assert f.parameters_per_chip(c) == 198_791_424 + 4 * 16 * 6_193_152 == 595_153_152
    assert round(16 * f.parameters_per_chip(c) / 1e9, 2) == 9.52


def test_the_built_net_has_that_many_parameters():
    """`parameters` against the leaves of the net the program builds from the
    same file (shapes only: nothing of 1.78 B parameters is allocated), and
    what a chip of four holds against the layers' own declaration."""
    import jax

    from deeplearning4j_tpu import zoo
    from deeplearning4j_tpu.models import MultiLayerNetwork

    f, c = load()
    args = {k: tuple(v) if isinstance(v, list) else v for k, v in c["program"]["args"].items()}
    net = MultiLayerNetwork(getattr(zoo, c["program"]["zoo"])(**args).conf())
    shapes = jax.eval_shape(lambda: net.init().params)
    count = sum(int(a.size) for a in jax.tree_util.tree_leaves(shapes))
    assert count == f.parameters(c) == 1_784_238_336
    ref = harness.module("reference", c["reference"])
    assert sum(math.prod(s) for s in ref.leaf_shapes(c).values()) == count
    assert f.layers(c) == ref.layers(c) == [True, True, True, False]
    names = {jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(shapes)}
    assert not any("Wg'" in n or "shared" in n for n in names)          # no gate, no shared expert
    paths = ref.program_paths(c)
    assert len(paths) == len(names) == 4 * 7 + 3
    held = 0
    for i, layer in enumerate(net.layers):
        specs = layer.partition_specs(shapes[f"layer_{i}"], {"data": 4})
        for spec, leaf in zip(jax.tree_util.tree_leaves(specs, is_leaf=lambda s: hasattr(s, "index")),
                              jax.tree_util.tree_leaves(shapes[f"layer_{i}"])):
            held += int(leaf.size) // (4 if tuple(spec) == ("data",) else 1)
    assert held == f.parameters_per_chip(c)
    for name, path in paths.items():
        leaf = shapes
        for k in path:
            leaf = leaf[k]
        assert tuple(leaf.shape) == ref.leaf_shapes(c)[name], name


def test_weights_per_token_and_the_step_by_hand():
    f, c = load()
    t = 8192
    want = 4 * (21_233_664 + 2304 * 64 + 8 * 6_193_152) + 2304 * 24576
    assert f.matmul_weights_per_token(c) == want
    tri, band = t * (t + 1) // 2, 1024 * 1025 // 2 + (t - 1024) * 1024
    assert f.band_scores(t, 1024) == band == sum(min(i + 1, 1024) for i in range(t))
    full, slide = 32 * tri * 12 * 128, 3 * 32 * band * 12 * 128
    assert f.window_flash_flops(c, 1) == slide and f.flash_flops(c, 1) == full + slide
    assert f.step_flops(c, 4) == int(6 * want * 4 * t + 4 * (full + slide))
    assert 75e12 < f.step_flops(c, 4) < 80e12               # ~78 TFLOP a step over the four chips
    one = t * 128 * 2 * ((2 * 32 + 2 * 4) + (4 * 32 + 4 * 4))
    assert f.window_flash_bytes(c, 1) == 3 * one and f.flash_bytes(c, 1) == 4 * one
    # a band of 1024 keys is bound by its operations on a v5e (197 TFLOP/s, 819 GB/s)
    assert f.window_flash_flops(c, 1) / 197e12 > 3 * f.window_flash_bytes(c, 1) / 819e9


def test_configuration_file_keeps_the_published_widths():
    _, c = load()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r["source_url"] == c["source"])
    assert row["name"] == "Mellum2-12B-A2.5B-Instruct"
    assert set(c["reduced"]) == {"num_hidden_layers", "vocab_size"}
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c["published"][key] == value, key
        else:
            assert c[key] == value, key
    assert (c["num_experts"], c["num_experts_per_tok"], c["expert_parallel"]) == (64, 8, 4)
    assert (c["num_hidden_layers"], c["layers_first"], c["vocab_size"]) == (4, 0, 24576)
    for phrase in ("each layer shared by 4 chips", "experts 4 ways with the exchange",
                   "1 784 238 336 parameters", "595 153 152 = 9.52 GB a chip"):
        assert phrase in c["deployment"], phrase
    assert {"router scores", "shared expert", "q/k norm", "gate", "window", "positions",
            "capacity_factor", "weights"} <= set(c["assumed"])
    assert any("MTP" in d for d in c["departures"])
    args = c["program"]["args"]
    for key in ("hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads",
                "layer_types", "sliding_window", "rope_parameters", "moe_intermediate_size",
                "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps", "vocab_size",
                "num_hidden_layers", "num_experts", "layers_first"):
        assert args[key] == c[key], key
    assert (args["head_gate"], args["shared_expert_intermediate_size"], args["mlp_only_layers"],
            args["moe_routed_scaling_factor"], args["expert_exchange_axis"]) == (
                False, 0, [], 1.0, "data")
    assert (args["remat"], args["max_length"], c["input"]["seq_len"]) == ("full", 8192, 8192)
