"""The residual blocks (nn/layers/blocks.py) wrap the LAYER they are given and
know none of its arguments: around layers no table of kinds ever listed, a
block inits, fits, round-trips its nested JSON to an equal configuration and
restores from a zip to equal outputs; what a layer inherits (`weight_init`)
is handed down where the nested layer has none of its own; the two older
wrappers read their `underlying` through the same `base.nested_layer`."""
import json

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models import MultiLayerNetwork, serialization
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn import updaters
from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import (
    LSTM,
    EmbeddingSequence,
    Frozen,
    GatedDeltaNet,
    GatedMLP,
    GatedShortConv,
    HybridBlock,
    LastTimeStep,
    RMSNorm,
    RnnOutput,
    SubLayerBlock,
)
from deeplearning4j_tpu.nn.layers.base import Layer, nested_layer

T, F, VOCAB = 24, 16, 11
IN = it.recurrent(F, T)

BLOCKS = {
    "sub_gated_delta_net": lambda: SubLayerBlock(
        sub=GatedDeltaNet(n_key_heads=2, n_value_heads=2, key_dim=8, value_dim=8), eps=1e-6),
    "sub_rms_norm": lambda: SubLayerBlock(sub=RMSNorm(eps=1e-6)),
    "hybrid_shortconv_mlp": lambda: HybridBlock(
        mixer=GatedShortConv(conv_width=3), moe=GatedMLP(width=8, act="relu2")),
}


@pytest.mark.parametrize("case", sorted(BLOCKS))
def test_a_block_wraps_any_registered_layer(case, tmp_path, rng):
    block = BLOCKS[case]()
    nested = [getattr(block, f) for f in ("sub", "mixer", "moe") if hasattr(block, f)]
    params = block.init_params(jax.random.PRNGKey(0), IN)
    assert set(params) == ({"norm", "sub"} if len(nested) == 1
                           else {"norm1", "mixer", "norm2", "moe"})
    for layer, key in zip(nested, [k for k in params if not k.startswith("norm")]):
        want = layer.init_params(jax.random.PRNGKey(1), IN)
        assert jax.tree.structure(params[key]) == jax.tree.structure(want)
    conf = NeuralNetConfiguration(seed=5, updater=updaters.Adam(learning_rate=1e-2)).list([
        EmbeddingSequence(n_in=VOCAB, n_out=F), block,
        RnnOutput(n_out=VOCAB, loss="mcxent", activation="softmax", has_bias=False),
    ]).set_input_type(it.recurrent(VOCAB, T))
    # the JSON nests the wrapped layer under its own type, and comes back equal
    text = conf.to_json()
    written = json.loads(text)["layers"][1]
    assert [written[f]["type"] for f in ("sub", "mixer", "moe") if f in written] == [
        type(layer).__name__ for layer in nested]
    again = MultiLayerConfiguration.from_json(text)
    assert again.to_json() == text
    assert again.layers[1] == block and all(isinstance(l, Layer) for l in nested)
    # two steps move the score; a zip gives the same network back
    net = MultiLayerNetwork(conf).init()
    ids = rng.integers(0, VOCAB, (2, T)).astype(np.int32)
    ds = DataSet(ids, np.roll(ids, -1, 1).astype(np.int32))
    first = net.score(ds)
    net.fit(ds)
    net.fit(ds)
    assert np.isfinite(net.score(ds)) and net.score(ds) < first
    path = str(tmp_path / f"{case}.zip")
    serialization.write_model(net, path)
    back = serialization.restore_multi_layer_network(path)
    np.testing.assert_array_equal(back.output(ids), net.output(ids))
    assert back.score(ds) == net.score(ds)


def test_a_block_hands_its_weight_init_down():
    key = jax.random.PRNGKey(2)
    plain = GatedMLP(width=8).init_params(key, IN)
    relu = GatedMLP(width=8, weight_init="relu").init_params(key, IN)
    assert not np.array_equal(plain["Wgu"], relu["Wgu"])
    block = SubLayerBlock(sub=GatedMLP(width=8), weight_init="relu")
    np.testing.assert_array_equal(block.init_params(key, IN)["sub"]["Wgu"], relu["Wgu"])
    assert block.sub.weight_init is None                     # the configuration is left as written
    own = SubLayerBlock(sub=GatedMLP(width=8, weight_init="xavier"), weight_init="relu")
    np.testing.assert_array_equal(own.init_params(key, IN)["sub"]["Wgu"], plain["Wgu"])
    both = HybridBlock(mixer=GatedShortConv(), moe=GatedMLP(width=8), weight_init="relu")
    np.testing.assert_array_equal(
        both.init_params(key, IN)["moe"]["Wgu"],
        GatedMLP(width=8, weight_init="relu").init_params(jax.random.split(key, 2)[1], IN)["Wgu"])


@pytest.mark.parametrize("wrapper", [LastTimeStep, Frozen])
def test_the_older_wrappers_read_their_layer_the_same_way(wrapper):
    inner = LSTM(n_out=4, activation="tanh")
    assert nested_layer(None) is None and nested_layer(inner) is inner
    assert nested_layer(inner.to_json()) == inner
    for given in (inner, inner.to_json()):
        layer = wrapper(underlying=given)
        assert layer._inner == inner
        assert Layer.from_json(layer.to_json())._inner == inner
    with pytest.raises(TypeError):
        wrapper(underlying="lstm")
