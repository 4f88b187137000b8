"""benchmark/flops/ouro.py against counts made by hand (ISSUE 44's count) and
against the parameter count of the net the program builds, which holds ONE
pass's leaves."""
import json
import math
import os

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load():
    with open(os.path.join(HERE, "configs", "ouro-2.6b-l6.json")) as f:
        return harness.module("flops", "ouro"), json.load(f)


def test_parameters_by_hand():
    f, c = load()
    per = f.layer_parameters(c)
    # [q | k | v] 2048 x 3 x 16 x 128 and o 2048 x 2048: four square matrices
    assert per["attention"] == 4 * 2048 ** 2 == 16_777_216
    assert per["mlp"] == 3 * 2048 * 5632 == 34_603_008
    layer = per["attention"] + per["mlp"] + 4 * 2048                   # four norms a layer
    assert layer == 51_388_416
    assert per["embedding"] == per["head"] == 49152 * 2048 == 100_663_296
    assert per["gate"] == 2049
    total = 6 * layer + 2 * 100_663_296 + 2048 + 2049
    assert 6 * layer == 308_330_496
    assert f.parameters(c) == total == 509_661_185                    # 509.7 M
    assert round(16 * total / 1e9, 2) == 8.15                         # GB at 16 B a parameter
    # published whole: 48 layers, no chip holds it
    whole = f.parameters(dict(c, num_hidden_layers=48))
    assert round(whole / 1e9, 3) == 2.668 and round(16 * whole / 1e9, 1) == 42.7
    # eight layers: 9.8 GB of state, left out
    assert round(16 * f.parameters(dict(c, num_hidden_layers=8)) / 1e9, 1) == 9.8


def test_the_built_net_has_one_passes_parameters():
    """`parameters` against the leaves of the net the program builds from the
    same file (shapes only: nothing of 510 M parameters is allocated): the
    looped stack holds its layers ONCE, whatever `total_ut_steps` says."""
    import jax

    from deeplearning4j_tpu import zoo
    from deeplearning4j_tpu.models import MultiLayerNetwork

    f, c = load()
    ref = harness.module("reference", c["reference"])
    for steps in (4, 1):
        args = dict(c["program"]["args"], total_ut_steps=steps)
        net = MultiLayerNetwork(getattr(zoo, c["program"]["zoo"])(**args).conf())
        shapes = jax.eval_shape(lambda: net.init().params)         # noqa: B023
        count = sum(int(a.size) for a in jax.tree_util.tree_leaves(shapes))
        assert count == f.parameters(c) == 509_661_185, steps
    assert sum(math.prod(s) for s in ref.leaf_shapes(c).values()) == count
    paths = ref.program_paths(c)
    assert len(paths) == len(jax.tree_util.tree_leaves(shapes)) == 8 * 6 + 5
    for name, path in paths.items():                                # every leaf, by name
        leaf = shapes
        for k in path:
            leaf = leaf[k]
        assert leaf.shape == ref.leaf_shapes(c)[name], name
    assert sorted(shapes["layer_1"], key=int) == [str(j) for j in range(13)]


def test_weights_per_token_by_hand():
    f, c = load()
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632                          # 51.38 M
    head = 2048 * 49152
    want = 4 * (6 * layer + head + 2048)
    assert f.matmul_weights_per_token(c) == want
    # one pass would be a quarter: the loop multiplies the blocks AND the head
    assert f.matmul_weights_per_token(dict(c, total_ut_steps=1)) * 4 == want


def test_step_is_100_tflop():
    f, c = load()
    t = 8192
    # Q K^T 2 t 128 + P V 2 t 128 a token and head forward, x 3 with the backward, halved by
    # the mask; 16 heads; 24 layer applications
    attn = 24 * t * 3 * t * 16 * (128 + 128)
    assert f.attention_flops(c, 1, t) == attn == f.flash_flops(c, 1)
    assert f.step_flops(c, 1) == int(6 * f.matmul_weights_per_token(c) * t + attn)
    assert 100.1e12 < f.step_flops(c, 1) < 100.3e12
    # ISSUE 44's reckoning, by part
    assert round(2 * 51_380_224 * t / 1e12, 3) == 0.842               # a layer's products, forward
    assert round(2 * t * t * 2048 / 1e12, 3) == 0.275                 # its causal attention, forward
    assert round(3 * 24 * (0.842 + 0.275), 1) == 80.4
    assert round(3 * 4 * 2 * t * 2048 * 49152 / 1e12, 1) == 19.8      # the four heads
    assert 19.7e12 < attn < 19.9e12                                   # flash: a fifth of the step
    assert abs(f.step_flops(c, 2) - 2 * f.step_flops(c, 1)) <= 1


def test_kernel_least_costs():
    f, c = load()
    t = 8192
    # bf16: forward q k v o, backward q k v o do dq dk dv, all at 16 heads; 24 applications
    assert f.flash_bytes(c, 1) == 24 * t * 128 * 2 * ((2 * 16 + 2 * 16) + (4 * 16 + 4 * 16))
    # attention at t 8192 is bound by its operations on a v5e (197 TFLOP/s, 819 GB/s)
    assert f.flash_flops(c, 1) / 197e12 > 8 * f.flash_bytes(c, 1) / 819e9


def test_configuration_file_keeps_the_published_widths():
    _, c = load()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r["source_url"] == c["source"])
    assert row["name"] == "Ouro-2.6B"
    assert c["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c["published"][key] == value, key
        else:
            assert c[key] == value, key
    assert (c["num_hidden_layers"], c["published"]["num_hidden_layers"]) == (6, 48)
    assert c["total_ut_steps"] == c["published"]["total_ut_steps"] == 4
    assert "circular pipeline of 8 stages of 6 layers" in c["deployment"]
    assert "509 661 185 parameters" in c["deployment"]
    assert {"attention", "norms", "gate", "beta", "optimizer", "weights", "bias"} <= set(c["assumed"])
    args = c["program"]["args"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
                "intermediate_size", "rms_norm_eps", "rope_theta", "total_ut_steps",
                "vocab_size", "num_hidden_layers", "beta"):
        assert args[key] == c[key], key
    assert (args["remat"], args["max_length"], c["input"]["seq_len"], c["input"]["vocab"]) == (
        "full", 8192, 8192, 49152)
