"""Zoo smoke tests — the TestInstantiation pattern (deeplearning4j-zoo
TestInstantiation.java: instantiate every zoo net, tiny fit/predict)."""
import os
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models import ComputationGraph, MultiLayerNetwork
from deeplearning4j_tpu.zoo import (
    VGG16,
    VGG19,
    AlexNet,
    Darknet19,
    FaceNetNN4Small2,
    GoogLeNet,
    InceptionResNetV1,
    LeNet,
    ResNet50,
    SimpleCNN,
    TextGenerationLSTM,
    TinyYOLO,
)

ALL_MODELS = [LeNet, SimpleCNN, AlexNet, VGG16, VGG19, ResNet50, Darknet19,
              TextGenerationLSTM, TinyYOLO, GoogLeNet, InceptionResNetV1,
              FaceNetNN4Small2]


@pytest.mark.parametrize("cls", ALL_MODELS)
def test_zoo_config_builds(cls):
    """Every zoo model's config builds and shape-infers."""
    m = cls()
    c = m.conf()
    from deeplearning4j_tpu.nn.graph_conf import ComputationGraphConfiguration

    if isinstance(c, ComputationGraphConfiguration):
        c.validate()
        assert c.vertex_output_types()
    else:
        c.validate()


def test_lenet_forward_and_fit(rng):
    net = LeNet().init()
    assert isinstance(net, MultiLayerNetwork)
    x = rng.standard_normal((4, 28, 28, 1)).astype(np.float32)
    out = net.output(x)
    assert out.shape == (4, 10)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 4)]
    net.fit(DataSet(x, y))
    assert np.isfinite(net.score_)


def test_simplecnn_forward(rng):
    net = SimpleCNN(num_classes=5).init()
    out = net.output(rng.standard_normal((2, 48, 48, 3)).astype(np.float32))
    assert out.shape == (2, 5)


def test_resnet50_small_input_forward(rng):
    net = ResNet50(num_classes=10, input_shape=(64, 64, 3)).init()
    assert isinstance(net, ComputationGraph)
    out = net.output(rng.standard_normal((2, 64, 64, 3)).astype(np.float32))
    assert out.shape == (2, 10)
    # ~23.5M params at 1000 classes; at 10 classes ~ 23.5M - 2M
    assert net.num_params() > 2e7


def test_text_generation_lstm_fit(rng):
    net = TextGenerationLSTM(num_classes=20, max_length=12).init()
    x = rng.standard_normal((2, 12, 20)).astype(np.float32)
    y = np.zeros((2, 12, 20), np.float32)
    y[..., 0] = 1.0
    net.fit(DataSet(x, y))
    assert np.isfinite(net.score_)
    assert net.output(x).shape == (2, 12, 20)


def test_googlenet_small_forward(rng):
    net = GoogLeNet(num_classes=7, input_shape=(64, 64, 3)).init()
    out = net.output(rng.standard_normal((1, 64, 64, 3)).astype(np.float32))
    assert out.shape == (1, 7)


def test_tinyyolo_loss_finite(rng):
    net = TinyYOLO(num_classes=3, input_shape=(64, 64, 3)).init()
    x = rng.standard_normal((1, 64, 64, 3)).astype(np.float32)
    # grid is 64/32 = 2x2; labels [b, 2, 2, 4+3]
    labels = np.zeros((1, 2, 2, 7), np.float32)
    labels[0, 0, 1] = [0.5, 0.0, 1.0, 0.5, 1, 0, 0]  # one object
    s = net.score(DataSet(x, labels))
    assert np.isfinite(s)
    net.fit(DataSet(x, labels))
    assert np.isfinite(net.score_)


def test_facenet_centerloss_builds(rng):
    net = FaceNetNN4Small2(num_classes=5, input_shape=(64, 64, 3)).init()
    out = net.output(rng.standard_normal((2, 64, 64, 3)).astype(np.float32))
    assert out.shape == (2, 5)


def test_init_pretrained_checksummed_fixture(tmp_path):
    """End-to-end ZooModel.initPretrained parity (ZooModel.java:64-81):
    a committed, Adler-32-checksummed LeNet weight zip loads from the
    cache, reproduces pinned outputs, and a corrupted archive fails its
    checksum, is deleted, and raises."""
    import shutil

    import numpy as np
    import pytest

    from deeplearning4j_tpu.zoo import LeNet

    fix = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "zoo")
    cache = tmp_path / "models"
    cache.mkdir()
    for f in ("lenet_mnist.zip", "lenet_mnist.zip.adler32"):
        shutil.copy(os.path.join(fix, f), cache / f)

    zm = LeNet(cache_dir=str(cache))
    assert zm.pretrained_available("mnist")
    net = zm.init_pretrained("mnist")

    exp = np.load(os.path.join(fix, "lenet_mnist_expected.npz"))
    out = np.asarray(net.output(exp["probe"]))
    np.testing.assert_allclose(out, exp["out"], atol=1e-5)

    # corruption -> checksum mismatch raises and removes the cache entry
    path = cache / "lenet_mnist.zip"
    blob = bytearray(path.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    path.write_bytes(bytes(blob))
    with pytest.raises(ValueError, match="Adler-32"):
        zm.init_pretrained("mnist")
    assert not path.exists()
    # the stale sidecar goes with it: a manually re-fetched replacement
    # archive must not be judged against the old sidecar and re-deleted
    assert not (cache / "lenet_mnist.zip.adler32").exists()

    # class-pinned checksum wins over the sidecar
    shutil.copy(os.path.join(fix, "lenet_mnist.zip"), path)
    zm_bad = LeNet(cache_dir=str(cache), checksums={"mnist": 12345})
    with pytest.raises(ValueError, match="Adler-32"):
        zm_bad.init_pretrained("mnist")


def test_vision_transformer_forward_and_fit(rng):
    """Net-new ViT zoo model: patch-conv tokens + non-causal transformer
    blocks + mean-pool head trains end to end."""
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.zoo import VisionTransformer

    zm = VisionTransformer(num_classes=5, input_shape=(16, 16, 3),
                           patch_size=4, d_model=32, n_heads=4, n_layers=2)
    net = zm.init()
    x = rng.standard_normal((8, 16, 16, 3)).astype(np.float32)
    out = net.output(x)
    assert out.shape == (8, 5)
    np.testing.assert_allclose(np.asarray(out).sum(1), 1.0, atol=1e-4)

    y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, 8)]
    ds = DataSet(x, y)
    before = net.score(ds)
    net.fit(ListDataSetIterator(ds, batch=8), epochs=30)
    assert net.score(ds) < before

    # config serde round-trips (preprocessor included)
    js = zm.conf().to_json()
    from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
    assert MultiLayerConfiguration.from_json(js).to_json() == js

    import pytest
    with pytest.raises(ValueError, match="patch"):
        VisionTransformer(input_shape=(30, 30, 3), patch_size=4).conf()


# ---------------------------------------------------------------------------
# the looped LM against the benchmark's plain reference (PR 44)
# ---------------------------------------------------------------------------
LOOP_LIMITS = {"loss_gap": 2e-6, "grad_norm_gap": 2e-4, "grad_norm_gap_median": 2e-5,
               "delta_norm_gap": 2e-3}


@pytest.fixture(scope="module")
def loop_lm_case():
    import jax

    from benchmark.reference import ouro as ref
    from benchmark.tests import tiny_ouro
    from benchmark.traffic import train_stream_ids as tsi

    cfg = tiny_ouro.ouro()
    seed = 2 ** 31 + 44
    data = tsi.make_batches(cfg, dict(tiny_ouro.TRAIN_IDS, distinct_batches=3), 2, seed)
    p0 = jax.device_get(ref.init_params(cfg, seed))
    want = tsi.reference_numbers(ref, cfg, p0, {}, data, 3)
    return cfg, ref, data, p0, want


def test_loop_lm_takes_the_references_three_adam_steps(loop_lm_case):
    """zoo.LoopLM -> config DSL -> `ParallelWrapper.fit` on integer labels
    against benchmark/reference/ouro.py (four explicit passes, float32): each
    loss, the first gradient of EVERY leaf as Adam got it, the parameters'
    change after three steps. `install` names every leaf of the program: ONE
    pass's."""
    import jax

    from benchmark import program
    from benchmark.reference import common
    from benchmark.traffic import train_stream as ts
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper
    from deeplearning4j_tpu.parallel.mesh import build_mesh

    cfg, ref, data, p0, want = loop_lm_case
    net = program.build_net(cfg)
    program.install(net, ref, cfg, p0, {})
    paths = ref.program_paths(cfg)
    layers = cfg["num_hidden_layers"]
    assert (len(jax.tree_util.tree_leaves(net.params)) == len(paths)
            == len(ref.leaf_shapes(cfg)) == 8 * layers + 5)
    assert net.num_params() == sum(int(np.prod(s)) for s in ref.leaf_shapes(cfg).values())
    log = ts.StepLog()
    net.set_listeners(log)
    pw = ParallelWrapper(net, mesh=build_mesh(MeshSpec(data=1), jax.devices()[:1]))
    stream = ts.make_stream([program.dataset(x, y) for x, y, _ in data], 2)
    got = ts.program_numbers(net, pw, stream, log, ref, cfg, p0, 3)
    rows = common.compare_training(got, want, LOOP_LIMITS, ref.COMPARISONS)
    assert all(r[3] for r in rows), rows
    # every leaf's gradient, not the worst alone; the gate's is no zero
    gaps = common.leaf_gaps(got["grad_norms"], want["grad_norms"])
    assert set(gaps) == set(paths) and max(gaps.values()) < 2e-4, gaps
    assert want["grad_norms"]["gate.w"] > 0 and want["grad_norms"]["gate.b"] > 0
    exits = telemetry.fit_log()[-1]["exit"]
    assert len(exits) == 1 and len(exits[0]["exit_p"]) == cfg["total_ut_steps"]
    assert abs(sum(exits[0]["exit_p"]) - 1.0) < 1e-5 and 1.0 < exits[0]["expected_passes"] < 4.0
    assert 0.0 < exits[0]["exit_entropy"] < np.log(4.0)


@pytest.mark.parametrize("control", ["three_passes", "last_pass_loss", "norm_outside"])
def test_each_fault_of_the_loop_is_another_model(control, loop_lm_case):
    """The reference's structural controls fail the limits the program holds."""
    from benchmark.reference import common
    from benchmark.traffic import train_stream_ids as tsi

    cfg, ref, data, p0, want = loop_lm_case
    other = tsi.reference_numbers(ref, cfg, p0, {}, data, 3, control)
    rows = common.compare_training(other, want, LOOP_LIMITS, ref.COMPARISONS)
    assert not all(r[3] for r in rows), rows
    # and by the limits the cell itself is held to on the chip
    rows = common.compare_training(other, want, ref.LIMITS, ref.COMPARISONS)
    assert not all(r[3] for r in rows), rows


# ---------------------------------------------------------------------------
# the LM whose attention layers are not alike against the benchmark's plain
# reference (PR 47)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def windowed_lm_case():
    import jax

    from benchmark.reference import laguna as ref
    from benchmark.tests import tiny_ids, tiny_laguna
    from benchmark.traffic import train_stream_ids as tsi

    cfg = tiny_laguna.laguna()      # 2 + 3 heads over 1 key/value head, window 8, t 32, 4 of 8 experts
    seed = 2 ** 31 + 47
    data = tsi.make_batches(cfg, dict(tiny_ids.TRAIN_IDS, distinct_batches=3), 2, seed)
    p0 = jax.device_get(ref.init_params(cfg, seed))
    want = tsi.reference_numbers(ref, cfg, p0, {}, data, 3)
    return cfg, ref, data, p0, want


def test_windowed_lm_takes_the_references_three_adam_steps(windowed_lm_case):
    """zoo.WindowedMoELM -> config DSL -> `ParallelWrapper.fit` on integer
    labels against benchmark/reference/laguna.py (masked softmax, float32):
    each loss, the first gradient of EVERY leaf as Adam got it, the
    parameters' change after three steps. `install` names every leaf."""
    import jax

    from benchmark import program
    from benchmark.reference import common
    from benchmark.traffic import train_stream as ts
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper
    from deeplearning4j_tpu.parallel.mesh import build_mesh

    cfg, ref, data, p0, want = windowed_lm_case
    net = program.build_net(cfg)
    program.install(net, ref, cfg, p0, {})
    paths = ref.program_paths(cfg)
    # 5 attention blocks of 4 leaves, a dense feed-forward of 3, 4 expert ones of 6, 3 outside
    assert (len(jax.tree_util.tree_leaves(net.params)) == len(paths)
            == len(ref.leaf_shapes(cfg)) == 5 * 4 + 3 + 4 * 6 + 3)
    assert net.num_params() == sum(int(np.prod(s)) for s in ref.leaf_shapes(cfg).values())
    log = ts.StepLog()
    net.set_listeners(log)
    pw = ParallelWrapper(net, mesh=build_mesh(MeshSpec(data=1), jax.devices()[:1]))
    stream = ts.make_stream([program.dataset(x, y) for x, y, _ in data], 2)
    got = ts.program_numbers(net, pw, stream, log, ref, cfg, p0, 3)
    rows = common.compare_training(got, want, LOOP_LIMITS, ref.COMPARISONS)
    assert all(r[3] for r in rows), rows
    gaps = common.leaf_gaps(got["grad_norms"], want["grad_norms"])
    assert set(gaps) == set(paths) and max(gaps.values()) < 2e-4, gaps
    assert all(want["grad_norms"][f"l{i}.attn.wg"] > 0 for i in range(5))
    fit = telemetry.fit_log()[-1]
    assert [a["layer"] for a in fit["attention"]] == ["layer_3", "layer_5", "layer_7"]
    assert all((a["window"], a["n_heads"], a["n_kv_heads"]) == (8, 3, 1) and
               0.0 < a["band_fill"] <= 1.0 for a in fit["attention"])
    assert len(fit["experts"]) == 4
    assert all(e["dropped_assignments"] == 0 for e in fit["experts"])


@pytest.mark.parametrize("control", ["drop_window", "window_511", "drop_yarn",
                                     "drop_rope_scale", "drop_gate"])
def test_each_fault_of_the_mixed_stack_is_another_model(control, windowed_lm_case):
    """The reference's structural controls fail the limits the program holds."""
    from benchmark.reference import common
    from benchmark.traffic import train_stream_ids as tsi

    cfg, ref, data, p0, want = windowed_lm_case
    assert control in ref.CONTROLS
    other = tsi.reference_numbers(ref, cfg, p0, {}, data, 3, control)
    rows = common.compare_training(other, want, LOOP_LIMITS, ref.COMPARISONS)
    assert not all(r[3] for r in rows), rows
    # and by the limits the cell itself is held to on the chip
    rows = common.compare_training(other, want, ref.LIMITS, ref.COMPARISONS)
    assert not all(r[3] for r in rows), rows


def test_windowed_lm_follows_the_published_lists_and_round_trips(tmp_path):
    import json

    import jax.numpy as jnp

    from benchmark.tests import tiny, tiny_laguna
    from deeplearning4j_tpu import zoo
    from deeplearning4j_tpu.models import MultiLayerNetwork, serialization
    from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
    from deeplearning4j_tpu.nn.layers import GatedAttention, GatedMLP, RoutedExperts, SubLayerBlock

    published = tiny.config("laguna-s-2.1-l5")
    args = {k: (tuple(v) if isinstance(v, list) else v)
            for k, v in tiny_laguna.laguna()["program"]["args"].items()}
    model = zoo.WindowedMoELM(**args)
    built = model.layers_built()
    assert [(i, kind) for i, kind, _ in built] == list(enumerate(published["layer_types"][:5]))
    assert [h for _, _, h in built] == [2, 3, 3, 3, 2]
    subs = [l.sub for l in model.conf().layers if isinstance(l, SubLayerBlock)]
    assert [type(s) for s in subs] == [GatedAttention, GatedMLP] + [GatedAttention,
                                                                     RoutedExperts] * 4
    full, sliding = subs[0], subs[2]
    assert (full.window, full.rotary_fraction, full.rope_theta, full.n_heads) == (None, 0.5, 100.0, 2)
    assert full.rope_scaling["rope_type"] == "yarn" and full.rope_scaling["factor"] == 8
    assert (sliding.window, sliding.rotary_fraction, sliding.rope_theta, sliding.n_heads,
            sliding.rope_scaling) == (8, 1.0, 10000.0, 3, None)
    assert all((a.gated, a.gate, a.qk_norm, a.n_kv_heads) == (True, "head", False, 1)
               for a in subs[::2])
    e = subs[3]
    assert (e.n_experts, e.top_k, e.held(), e.shared_width, e.scoring, e.norm_topk,
            e.routed_scale, e.shared_gated) == (8, 3, (2, 4), 16, "softmax", True, 2.5, False)
    # a later pipeline stage: the published lists are indexed from layers_first
    later = zoo.WindowedMoELM(**dict(args, layers_first=3, num_hidden_layers=2))
    assert [(i, kind, h) for i, kind, h in later.layers_built()] == [
        (3, "sliding_attention", 3), (4, "full_attention", 2)]
    assert all(isinstance(s, RoutedExperts) for s in later.sublayers()[1::2])
    with pytest.raises(ValueError, match=r"chunked_attention.*none of"):
        zoo.WindowedMoELM(layer_types=["full_attention", "chunked_attention"],
                          num_hidden_layers=2).layers_built()
    with pytest.raises(ValueError, match="48 entries"):
        zoo.WindowedMoELM(**dict(args, num_hidden_layers=49)).layers_built()
    conf = model.conf()
    again = MultiLayerConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    nested = [l["sub"] for l in json.loads(conf.to_json())["layers"] if l["type"] == "SubLayerBlock"]
    assert nested[2]["window"] == 8 and "window" not in nested[0]
    assert nested[0]["rope_scaling"]["attention_factor"] == 1.4852030263919618
    net = MultiLayerNetwork(conf).init()
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 48, (2, 32)), jnp.int32)
    want = net.output(ids)
    path = str(tmp_path / "laguna.zip")
    serialization.write_model(net, path)
    np.testing.assert_allclose(serialization.restore_multi_layer_network(path).output(ids), want,
                               atol=1e-6)
