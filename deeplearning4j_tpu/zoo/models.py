"""Model zoo — the 12 architectures of deeplearning4j-zoo/src/main/java/org/
deeplearning4j/zoo/model/ (AlexNet.java:157, Darknet19.java:220,
FaceNetNN4Small2.java:362, GoogLeNet.java:197, InceptionResNetV1.java:324,
LeNet.java:129, ResNet50.java:239, SimpleCNN.java:152,
TextGenerationLSTM.java:111, TinyYOLO.java:254, VGG16.java:181,
VGG19.java:172), re-expressed as configs of this framework (NHWC layouts,
ComputationGraph for DAG nets).

Each ZooModel builds a fresh config via `conf()` and an initialized network
via `init()` (ZooModel.java:23-81's init()). Pretrained-weight download is
environment-gated (zero-egress images have no network); `init_pretrained`
loads from a local cache path when present (PretrainedType semantics).
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

from deeplearning4j_tpu.models import ComputationGraph, MultiLayerNetwork
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn import updaters
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph_vertices import (
    ElementWiseVertex,
    L2NormalizeVertex,
    MergeVertex,
)
from deeplearning4j_tpu.nn.layers import (
    LSTM,
    LRN,
    Activation,
    BatchNorm,
    Conv2D,
    Dense,
    DropoutLayer,
    EmbeddingSequence,
    GatedAttention,
    GatedDeltaNet,
    GatedMLP,
    GatedShortConv,
    GlobalPooling,
    GravesLSTM,
    HybridBlock,
    KimiDeltaAttention,
    LatentAttention,
    LoopedStack,
    LoopExitOutput,
    Mamba2Mixer,
    Output,
    RMSNorm,
    RnnOutput,
    RoutedExperts,
    SeparableConv2D,
    SubLayerBlock,
    Subsampling2D,
    ZeroPadding2D,
)


@dataclass
class ZooModel:
    """Base: numClasses/seed/inputShape + init()/init_pretrained()."""

    num_classes: int = 1000
    seed: int = 123
    input_shape: Tuple[int, int, int] = (224, 224, 3)  # H, W, C
    cache_dir: str = field(
        default_factory=lambda: os.path.expanduser("~/.deeplearning4j_tpu/models")
    )

    def conf(self):
        raise NotImplementedError

    def init(self):
        c = self.conf()
        from deeplearning4j_tpu.nn.graph_conf import ComputationGraphConfiguration

        if isinstance(c, ComputationGraphConfiguration):
            return ComputationGraph(c).init()
        return MultiLayerNetwork(c).init()

    #: Class-level Adler-32 pins for OFFICIAL pretrained archives, keyed by
    #: kind (ZooModel.pretrainedChecksum; 0/absent = no verification).
    #: Subclasses with published weights override PINNED_CHECKSUMS; the
    #: `checksums` field adds/overrides per-instance pins and is merged
    #: with the class pins in __post_init__ (a dataclass field default
    #: would silently shadow a subclass class-attribute).
    PINNED_CHECKSUMS = {}

    checksums: dict = field(default_factory=dict, repr=False)

    def __post_init__(self):
        merged = dict(type(self).PINNED_CHECKSUMS)
        merged.update(self.checksums)
        self.checksums = merged

    def pretrained_available(self, kind: str = "imagenet") -> bool:
        return os.path.exists(self._pretrained_path(kind))

    def _pretrained_path(self, kind: str) -> str:
        return os.path.join(self.cache_dir,
                            f"{type(self).__name__.lower()}_{kind}.zip")

    def _expected_checksum(self, path: str, kind: str) -> Optional[int]:
        """Class-pinned checksum first (official archives), else the
        `.adler32` sidecar save_pretrained() writes next to the zip."""
        if self.checksums.get(kind):
            return int(self.checksums[kind])
        sidecar = path + ".adler32"
        if os.path.exists(sidecar):
            with open(sidecar) as f:
                return int(f.read().strip())
        return None

    @staticmethod
    def _adler32(path: str) -> int:
        import zlib

        value = 1
        with open(path, "rb") as f:
            while True:
                chunk = f.read(1 << 20)
                if not chunk:
                    break
                value = zlib.adler32(chunk, value)
        return value

    def save_pretrained(self, net, kind: str = "imagenet") -> str:
        """Write `net` into this model's cache slot with an Adler-32
        sidecar, so a later init_pretrained() is checksum-verified — the
        local-cache analogue of publishing a checksummed archive."""
        from deeplearning4j_tpu.models import write_model

        os.makedirs(self.cache_dir, exist_ok=True)
        path = self._pretrained_path(kind)
        write_model(net, path)
        with open(path + ".adler32", "w") as f:
            f.write(str(self._adler32(path)))
        return path

    def init_pretrained(self, kind: str = "imagenet"):
        """Load cached pretrained weights with checksum verification
        (ZooModel.initPretrained + pretrainedChecksum semantics,
        ZooModel.java:64-81: Adler-32 over the archive; on mismatch the
        corrupt cache entry is deleted and the load fails). Download is
        impossible in zero-egress environments, so only the local cache
        path is honored."""
        path = self._pretrained_path(kind)
        if not os.path.exists(path):
            raise FileNotFoundError(
                f"No cached pretrained weights at {path}; this environment "
                f"has no network egress to download them."
            )
        expected = self._expected_checksum(path, kind)
        if expected is not None:
            actual = self._adler32(path)
            if actual != expected:
                os.remove(path)
                if not self.checksums.get(kind):
                    # The expectation came from the sidecar, which is now
                    # stale — a re-fetched replacement archive must not be
                    # compared against it (and deleted again). Class pins
                    # stay authoritative and are never removed. Trade-off:
                    # a replacement will load UNVERIFIED until re-saved
                    # via save_pretrained or pinned via `checksums`.
                    sidecar = path + ".adler32"
                    if os.path.exists(sidecar):
                        os.remove(sidecar)
                raise ValueError(
                    f"Pretrained archive {path} failed its Adler-32 check "
                    f"(got {actual}, expected {expected}); the corrupt "
                    f"cache entry and its sidecar were removed — re-fetch "
                    f"the weights (the replacement loads unverified unless "
                    f"re-saved with save_pretrained or pinned via "
                    f"`checksums`)")
        from deeplearning4j_tpu.models import restore_model

        return restore_model(path)


@dataclass
class LeNet(ZooModel):
    """LeNet-5 on MNIST-sized input (zoo/model/LeNet.java:129)."""

    num_classes: int = 10
    input_shape: Tuple[int, int, int] = (28, 28, 1)

    def conf(self):
        h, w, c = self.input_shape
        return NeuralNetConfiguration(
            seed=self.seed, updater=updaters.Adam(learning_rate=1e-3),
            weight_init="xavier", activation="identity",
        ).list([
            Conv2D(kernel_size=(5, 5), stride=(1, 1), n_out=20,
                   activation="identity", convolution_mode="same"),
            Subsampling2D(kernel_size=(2, 2), stride=(2, 2), pooling_type="max"),
            Conv2D(kernel_size=(5, 5), stride=(1, 1), n_out=50,
                   activation="identity", convolution_mode="same"),
            Subsampling2D(kernel_size=(2, 2), stride=(2, 2), pooling_type="max"),
            Dense(n_out=500, activation="relu"),
            Output(n_out=self.num_classes, loss="mcxent", activation="softmax"),
        ]).set_input_type(it.convolutional(h, w, c))


@dataclass
class SimpleCNN(ZooModel):
    """Compact CNN (zoo/model/SimpleCNN.java:152)."""

    num_classes: int = 10
    input_shape: Tuple[int, int, int] = (48, 48, 3)

    def conf(self):
        h, w, c = self.input_shape
        return NeuralNetConfiguration(
            seed=self.seed, updater=updaters.AdaDelta(),
            activation="relu", weight_init="relu",
        ).list([
            Conv2D(kernel_size=(7, 7), n_out=16, convolution_mode="same",
                   activation="relu"),
            BatchNorm(),
            Subsampling2D(kernel_size=(2, 2), pooling_type="max"),
            Conv2D(kernel_size=(5, 5), n_out=32, convolution_mode="same",
                   activation="relu"),
            BatchNorm(),
            Subsampling2D(kernel_size=(2, 2), pooling_type="max"),
            Conv2D(kernel_size=(3, 3), n_out=64, convolution_mode="same",
                   activation="relu"),
            BatchNorm(),
            Subsampling2D(kernel_size=(2, 2), pooling_type="max"),
            Dense(n_out=256, activation="relu", dropout=0.5),
            Output(n_out=self.num_classes, loss="mcxent"),
        ]).set_input_type(it.convolutional(h, w, c))


@dataclass
class AlexNet(ZooModel):
    """AlexNet (zoo/model/AlexNet.java:157)."""

    def conf(self):
        h, w, c = self.input_shape
        return NeuralNetConfiguration(
            seed=self.seed,
            updater=updaters.Nesterovs(learning_rate=1e-2, momentum=0.9),
            weight_init="normal", l2=5e-4,
        ).list([
            Conv2D(kernel_size=(11, 11), stride=(4, 4), n_out=96,
                   activation="relu"),
            LRN(),
            Subsampling2D(kernel_size=(3, 3), stride=(2, 2), pooling_type="max"),
            Conv2D(kernel_size=(5, 5), n_out=256, convolution_mode="same",
                   activation="relu", bias_init=1.0),
            LRN(),
            Subsampling2D(kernel_size=(3, 3), stride=(2, 2), pooling_type="max"),
            Conv2D(kernel_size=(3, 3), n_out=384, convolution_mode="same",
                   activation="relu"),
            Conv2D(kernel_size=(3, 3), n_out=384, convolution_mode="same",
                   activation="relu", bias_init=1.0),
            Conv2D(kernel_size=(3, 3), n_out=256, convolution_mode="same",
                   activation="relu", bias_init=1.0),
            Subsampling2D(kernel_size=(3, 3), stride=(2, 2), pooling_type="max"),
            Dense(n_out=4096, activation="relu", dropout=0.5, bias_init=1.0),
            Dense(n_out=4096, activation="relu", dropout=0.5, bias_init=1.0),
            Output(n_out=self.num_classes, loss="mcxent"),
        ]).set_input_type(it.convolutional(h, w, c))


def _vgg_blocks(spec):
    layers = []
    for n_convs, channels in spec:
        for _ in range(n_convs):
            layers.append(Conv2D(kernel_size=(3, 3), n_out=channels,
                                 convolution_mode="same", activation="relu"))
        layers.append(Subsampling2D(kernel_size=(2, 2), stride=(2, 2),
                                    pooling_type="max"))
    return layers


@dataclass
class VGG16(ZooModel):
    """VGG-16 (zoo/model/VGG16.java:181)."""

    def conf(self):
        h, w, c = self.input_shape
        layers = _vgg_blocks([(2, 64), (2, 128), (3, 256), (3, 512), (3, 512)])
        layers += [
            Dense(n_out=4096, activation="relu", dropout=0.5),
            Dense(n_out=4096, activation="relu", dropout=0.5),
            Output(n_out=self.num_classes, loss="mcxent"),
        ]
        return NeuralNetConfiguration(
            seed=self.seed,
            updater=updaters.Nesterovs(learning_rate=1e-2, momentum=0.9),
        ).list(layers).set_input_type(it.convolutional(h, w, c))


@dataclass
class VGG19(ZooModel):
    """VGG-19 (zoo/model/VGG19.java:172)."""

    def conf(self):
        h, w, c = self.input_shape
        layers = _vgg_blocks([(2, 64), (2, 128), (4, 256), (4, 512), (4, 512)])
        layers += [
            Dense(n_out=4096, activation="relu", dropout=0.5),
            Dense(n_out=4096, activation="relu", dropout=0.5),
            Output(n_out=self.num_classes, loss="mcxent"),
        ]
        return NeuralNetConfiguration(
            seed=self.seed,
            updater=updaters.Nesterovs(learning_rate=1e-2, momentum=0.9),
        ).list(layers).set_input_type(it.convolutional(h, w, c))


@dataclass
class ResNet50(ZooModel):
    """ResNet-50 (zoo/model/ResNet50.java:239) as a ComputationGraph with
    identity/conv shortcut bottleneck blocks. The BASELINE north-star model."""

    def conf(self):
        h, w, c = self.input_shape
        g = NeuralNetConfiguration(
            seed=self.seed,
            updater=updaters.Nesterovs(learning_rate=1e-1, momentum=0.9),
            weight_init="relu", l2=1e-4, activation="identity",
        ).graph().add_inputs("in")

        def conv_bn(name, inp, kernel, n_out, stride=(1, 1), act="relu",
                    mode="same"):
            g.add_layer(f"{name}_conv",
                        Conv2D(kernel_size=kernel, stride=stride, n_out=n_out,
                               convolution_mode=mode, has_bias=False), inp)
            g.add_layer(f"{name}_bn", BatchNorm(activation=act), f"{name}_conv")
            return f"{name}_bn"

        def bottleneck(name, inp, filters, stride, project):
            f1, f2, f3 = filters
            x = conv_bn(f"{name}_a", inp, (1, 1), f1, stride)
            x = conv_bn(f"{name}_b", x, (3, 3), f2)
            x = conv_bn(f"{name}_c", x, (1, 1), f3, act="identity")
            if project:
                sc = conv_bn(f"{name}_sc", inp, (1, 1), f3, stride,
                             act="identity")
            else:
                sc = inp
            g.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), x, sc)
            g.add_layer(f"{name}_relu", Activation(activation="relu"),
                        f"{name}_add")
            return f"{name}_relu"

        x = conv_bn("stem", "in", (7, 7), 64, (2, 2))
        g.add_layer("stem_pool",
                    Subsampling2D(kernel_size=(3, 3), stride=(2, 2),
                                  convolution_mode="same",
                                  pooling_type="max"), x)
        x = "stem_pool"
        stages = [
            ("s2", [64, 64, 256], 3, (1, 1)),
            ("s3", [128, 128, 512], 4, (2, 2)),
            ("s4", [256, 256, 1024], 6, (2, 2)),
            ("s5", [512, 512, 2048], 3, (2, 2)),
        ]
        for sname, filters, blocks, stride in stages:
            x = bottleneck(f"{sname}_0", x, filters, stride, project=True)
            for b in range(1, blocks):
                x = bottleneck(f"{sname}_{b}", x, filters, (1, 1),
                               project=False)
        g.add_layer("avgpool", GlobalPooling(pooling_type="avg"), x)
        g.add_layer("out", Output(n_out=self.num_classes, loss="mcxent"),
                    "avgpool")
        g.set_outputs("out")
        g.set_input_types(it.convolutional(h, w, c))
        return g


@dataclass
class Darknet19(ZooModel):
    """Darknet-19 (zoo/model/Darknet19.java:220)."""

    def conf(self):
        h, w, c = self.input_shape

        def conv_unit(n_out, k):
            return [
                Conv2D(kernel_size=(k, k), n_out=n_out, convolution_mode="same",
                       has_bias=False, activation="identity"),
                BatchNorm(activation="leakyrelu"),
            ]

        layers = []
        layers += conv_unit(32, 3)
        layers.append(Subsampling2D(kernel_size=(2, 2), stride=(2, 2)))
        layers += conv_unit(64, 3)
        layers.append(Subsampling2D(kernel_size=(2, 2), stride=(2, 2)))
        layers += conv_unit(128, 3) + conv_unit(64, 1) + conv_unit(128, 3)
        layers.append(Subsampling2D(kernel_size=(2, 2), stride=(2, 2)))
        layers += conv_unit(256, 3) + conv_unit(128, 1) + conv_unit(256, 3)
        layers.append(Subsampling2D(kernel_size=(2, 2), stride=(2, 2)))
        layers += (conv_unit(512, 3) + conv_unit(256, 1) + conv_unit(512, 3)
                   + conv_unit(256, 1) + conv_unit(512, 3))
        layers.append(Subsampling2D(kernel_size=(2, 2), stride=(2, 2)))
        layers += (conv_unit(1024, 3) + conv_unit(512, 1) + conv_unit(1024, 3)
                   + conv_unit(512, 1) + conv_unit(1024, 3))
        layers.append(Conv2D(kernel_size=(1, 1), n_out=self.num_classes,
                             convolution_mode="same", activation="identity"))
        layers.append(GlobalPooling(pooling_type="avg"))
        layers.append(Output(n_out=self.num_classes, loss="mcxent",
                             activation="softmax", has_bias=True,
                             n_in=self.num_classes))
        return NeuralNetConfiguration(
            seed=self.seed,
            updater=updaters.Nesterovs(learning_rate=1e-3, momentum=0.9),
            l2=5e-4,
        ).list(layers).set_input_type(it.convolutional(h, w, c))


@dataclass
class TextGenerationLSTM(ZooModel):
    """Char-level 2xLSTM generator (zoo/model/TextGenerationLSTM.java:111).
    GravesLSTM path — the BASELINE char-RNN config."""

    num_classes: int = 77  # vocab size
    max_length: int = 40

    def conf(self):
        return NeuralNetConfiguration(
            seed=self.seed, updater=updaters.RmsProp(learning_rate=1e-2),
            l2=1e-4,
        ).list([
            GravesLSTM(n_out=256, activation="tanh"),
            GravesLSTM(n_out=256, activation="tanh"),
            RnnOutput(n_out=self.num_classes, loss="mcxent",
                      activation="softmax"),
        ]).set_input_type(it.recurrent(self.num_classes, self.max_length))


@dataclass
class TransformerLM(ZooModel):
    """Decoder-only transformer LM — net-new 13th zoo architecture (the
    reference zoo is pre-transformer; SURVEY.md §5). Single-chip flavor of
    parallel/transformer.py's ShardedTransformerLM, built from the layer
    library so it composes with fit/output/serialization like every zoo net.
    Input: [b, t] token ids (EmbeddingSequence)."""

    num_classes: int = 1000  # vocab
    max_length: int = 128
    d_model: int = 256
    n_heads: int = 8
    n_layers: int = 4
    # per-block activation-checkpoint policy
    # ('none'|'dots_saveable'|'full'|'offload'; parallel/layout.py)
    remat: Optional[str] = None

    def conf(self):
        from deeplearning4j_tpu.nn.layers import (
            EmbeddingSequence,
            PositionEmbedding,
            TransformerBlock,
        )

        blocks = [
            TransformerBlock(n_heads=self.n_heads, causal=True,
                             remat=self.remat)
            for _ in range(self.n_layers)
        ]
        return NeuralNetConfiguration(
            seed=self.seed, updater=updaters.Adam(learning_rate=3e-4),
            weight_init="xavier",
        ).list([
            EmbeddingSequence(n_in=self.num_classes, n_out=self.d_model),
            PositionEmbedding(max_len=self.max_length),
            *blocks,
            RnnOutput(n_out=self.num_classes, loss="mcxent",
                      activation="softmax"),
        ]).set_input_type(it.recurrent(self.num_classes, self.max_length))


@dataclass
class _DecoderLM(ZooModel):
    """The ONE decoder skeleton: token ids -> `EmbeddingSequence` -> a residual
    block around each entry of `sublayers()` -> final RMS norm -> an untied
    bias-free head; Adam 3e-4, xavier, `remat` on every block. A model is the
    keys of its published `config.json` as fields, `NORM`, and `sublayers()`:
    the nested layers in network order, each built with every argument the
    model means — a Layer is wrapped in a `SubLayerBlock` (plain pre-norm),
    a (mixer, experts) pair in ONE `HybridBlock` (zero-centred pre-norms).
    `num_experts` is the count this rank HOLDS of `num_experts_published`
    (default: all of them), starting at `experts_first` (`_experts`). Input:
    [b, t] token ids; labels: [b, t] integer next-token ids (or dense
    one-hot)."""

    vocab_size: int = 1000
    hidden_size: int = 256
    max_length: int = 128
    # routed experts: held of published
    num_experts: int = 8
    num_experts_published: Optional[int] = None
    experts_first: int = 0
    capacity_factor: float = 1.25
    # per-block activation-checkpoint policy (parallel/layout.py)
    remat: Optional[str] = None

    #: the published key of every norm's eps; is the final norm's weight
    #: zero-centred (1 + w)
    NORM = ("rms_norm_eps", False)

    def sublayers(self):
        raise NotImplementedError

    def _experts(self, **recipe):
        """`RoutedExperts` over the experts this rank holds of the published
        count; `recipe` is everything else, spelled out by the model."""
        return RoutedExperts(
            n_experts=self.num_experts_published or self.num_experts,
            experts_held=(self.experts_first, self.num_experts),
            capacity_factor=self.capacity_factor, **recipe)

    def blocks(self, **block_args):
        """The residual blocks and the final norm, in network order;
        `block_args` go to every `SubLayerBlock`."""
        key, zero_centered = self.NORM
        eps = getattr(self, key)
        return [
            HybridBlock(mixer=sub[0], moe=sub[1], eps=eps, remat=self.remat)
            if isinstance(sub, tuple)
            else SubLayerBlock(sub=sub, eps=eps, remat=self.remat, **block_args)
            for sub in self.sublayers()
        ] + [RMSNorm(eps=eps, zero_centered=zero_centered)]

    def network(self, layers):
        """Token ids -> the embedding -> `layers`, under the skeleton's
        optimizer and initialisation."""
        return NeuralNetConfiguration(
            seed=self.seed, updater=updaters.Adam(learning_rate=3e-4),
            weight_init="xavier",
        ).list([
            EmbeddingSequence(n_in=self.vocab_size, n_out=self.hidden_size),
            *layers,
        ]).set_input_type(it.recurrent(self.vocab_size, self.max_length))

    def conf(self):
        return self.network([
            *self.blocks(),
            RnnOutput(n_out=self.vocab_size, loss="mcxent",
                      activation="softmax", has_bias=False),
        ])


@dataclass
class HybridMoELM(_DecoderLM):
    """Decoder-only LM of `HybridBlock`s: gated-delta-rule linear attention
    with a gated softmax-attention layer every `full_attention_interval`-th
    block, softmax-routed swiglu experts beside a gated shared expert in
    every block, zero-centred RMS norms (the Qwen3-Next shape)."""

    num_hidden_layers: int = 4
    full_attention_interval: int = 4
    rms_norm_eps: float = 1e-6
    # gated softmax attention
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 64
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # gated delta rule
    linear_num_key_heads: int = 2
    linear_num_value_heads: int = 4
    linear_key_head_dim: int = 32
    linear_value_head_dim: int = 32
    linear_conv_kernel_dim: int = 4
    # routed experts
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 64
    shared_expert_intermediate_size: int = 64
    norm_topk_prob: bool = True

    NORM = ("rms_norm_eps", True)

    def mixer_kinds(self):
        """The per-layer list of mixer kinds."""
        return ["attention" if (i + 1) % self.full_attention_interval == 0
                else "delta" for i in range(self.num_hidden_layers)]

    def sublayers(self):
        eps = self.rms_norm_eps
        mixer = {
            "attention": lambda: GatedAttention(
                n_heads=self.num_attention_heads, n_kv_heads=self.num_key_value_heads,
                head_dim=self.head_dim, rotary_fraction=self.partial_rotary_factor,
                rope_theta=self.rope_theta, eps=eps, gated=True, qk_norm=True,
                qk_norm_zero_centered=True),
            "delta": lambda: GatedDeltaNet(
                n_key_heads=self.linear_num_key_heads,
                n_value_heads=self.linear_num_value_heads,
                key_dim=self.linear_key_head_dim, value_dim=self.linear_value_head_dim,
                conv_width=self.linear_conv_kernel_dim, eps=eps),
        }
        return [(mixer[kind](), self._experts(
            top_k=self.num_experts_per_tok, expert_width=self.moe_intermediate_size,
            shared_width=self.shared_expert_intermediate_size,
            norm_topk=self.norm_topk_prob, scoring="softmax", routed_scale=1.0,
            expert_act="swiglu", shared_gated=True, norm_eps=1e-20))
            for kind in self.mixer_kinds()]


#: sub-layer kinds by the character a layer pattern names them with
PATTERN_KINDS = {"M": "mamba", "*": "attention", "E": "experts"}


def pattern_kinds(pattern: str):
    """"MEM*E" -> ["mamba", "experts", "mamba", "attention", "experts"]."""
    bad = sorted(set(pattern) - set(PATTERN_KINDS))
    if bad or not pattern:
        raise ValueError(f"layer pattern {pattern!r}: characters {bad} are none of "
                         f"{sorted(PATTERN_KINDS)}")
    return [PATTERN_KINDS[ch] for ch in pattern]


@dataclass
class PatternHybridLM(_DecoderLM):
    """Decoder-only LM whose layers are named by a PATTERN string, one
    character a layer, each ONE sub-layer: `M` a Mamba-2 state-space mixer,
    `*` grouped-query softmax attention without gate, q/k norms or positions,
    `E` sigmoid-routed relu^2 experts beside an ungated shared expert (the
    `nemotron_h` shape; `num_experts_published` is its `n_routed_experts`)."""

    hybrid_override_pattern: str = "MEM*E"
    layer_norm_epsilon: float = 1e-5
    # softmax attention
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 64
    # state-space mixer
    mamba_num_heads: int = 8
    mamba_head_dim: int = 32
    n_groups: int = 2
    ssm_state_size: int = 16
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 1e-3
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # routed experts
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 64
    moe_shared_expert_intermediate_size: int = 128
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True

    NORM = ("layer_norm_epsilon", False)

    def sublayers(self):
        eps = self.layer_norm_epsilon
        sub = {
            "mamba": lambda: Mamba2Mixer(
                n_heads=self.mamba_num_heads, head_dim=self.mamba_head_dim,
                n_groups=self.n_groups, state_dim=self.ssm_state_size,
                conv_width=self.conv_kernel, chunk=self.chunk_size, eps=eps,
                dt_min=self.time_step_min, dt_max=self.time_step_max,
                dt_floor=self.time_step_floor),
            "attention": lambda: GatedAttention(
                n_heads=self.num_attention_heads, n_kv_heads=self.num_key_value_heads,
                head_dim=self.head_dim, rotary_fraction=0.0, eps=eps, gated=False,
                qk_norm=False, qk_norm_zero_centered=False),
            "experts": lambda: self._experts(
                top_k=self.num_experts_per_tok, expert_width=self.moe_intermediate_size,
                shared_width=self.moe_shared_expert_intermediate_size,
                norm_topk=self.norm_topk_prob, scoring="sigmoid",
                routed_scale=self.routed_scaling_factor, expert_act="relu2",
                shared_gated=False, norm_eps=1e-20),
        }
        return [sub[kind]() for kind in pattern_kinds(self.hybrid_override_pattern)]


@dataclass
class DeltaLatentMoELM(_DecoderLM):
    """Decoder-only LM with a per-layer MIXER LIST and a leading dense
    layer, two sub-layers a layer: layer i (from 1) mixes with delta-rule
    linear attention whose decay is a vector over the key channels (KDA) if
    i is in `linear_attn_config["kda_layers"]`, else with latent attention
    (MLA); its feed-forward is a dense swiglu of `intermediate_size` for i <=
    `first_k_dense_replace`, else sigmoid-routed swiglu experts beside
    ungated shared experts. Two published shapes: `kimi_linear` — KDA layers
    carry the positions and the latent layers know none (`mla_use_nope`) —
    and `deepseek_v3` — `kda_layers` empty, EVERY layer latent attention with
    rotary positions (`rope_theta`, `rope_interleave`) on the rope part of
    its queries and on the key part all heads share (`mla_use_nope` false)."""

    num_hidden_layers: int = 5
    rms_norm_eps: float = 1e-5
    # which layer mixes how: {"kda_layers": [1, 2, 3, 5, ...], "num_heads",
    # "head_dim", "short_conv_kernel_size"}; the layers it does not list
    # are latent attention
    linear_attn_config: Optional[dict] = None
    # latent attention; without `mla_use_nope`, rotary positions on its
    # qk_rope_head_dim parts
    num_attention_heads: int = 4
    kv_lora_rank: int = 64
    qk_nope_head_dim: int = 32
    qk_rope_head_dim: int = 16
    v_head_dim: int = 32
    mla_use_nope: bool = True
    rope_theta: float = 10000.0
    rope_interleave: bool = True
    # feed-forward
    first_k_dense_replace: int = 1
    intermediate_size: int = 512
    # routed experts
    num_experts_per_token: int = 2
    moe_intermediate_size: int = 64
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    moe_renormalize: bool = True

    def sublayer_kinds(self):
        """The per-layer (mixer, feed-forward) kinds."""
        linear = self.linear_attn_config or {"kda_layers": [
            i for i in range(1, self.num_hidden_layers + 1) if i % 4]}
        return [("kda" if i in linear["kda_layers"] else "latent",
                 "dense" if i <= self.first_k_dense_replace else "experts")
                for i in range(1, self.num_hidden_layers + 1)]

    def sublayers(self):
        eps, linear = self.rms_norm_eps, self.linear_attn_config or {}
        sub = {
            "kda": lambda: KimiDeltaAttention(
                n_heads=linear.get("num_heads", 4), head_dim=linear.get("head_dim", 32),
                conv_width=linear.get("short_conv_kernel_size", 4), eps=eps),
            "latent": lambda: LatentAttention(
                n_heads=self.num_attention_heads, kv_rank=self.kv_lora_rank,
                nope_dim=self.qk_nope_head_dim, rope_dim=self.qk_rope_head_dim,
                v_dim=self.v_head_dim, eps=eps,
                rope_theta=None if self.mla_use_nope else float(self.rope_theta),
                rope_interleave=self.rope_interleave),
            "dense": lambda: GatedMLP(width=self.intermediate_size, act="swiglu"),
            "experts": lambda: self._experts(
                top_k=self.num_experts_per_token, expert_width=self.moe_intermediate_size,
                shared_width=self.num_shared_experts * self.moe_intermediate_size,
                norm_topk=self.moe_renormalize, scoring="sigmoid",
                routed_scale=self.routed_scaling_factor, expert_act="swiglu",
                shared_gated=False, norm_eps=1e-20),
        }
        return [sub[kind]() for pair in self.sublayer_kinds() for kind in pair]


@dataclass
class ShortConvMoELM(_DecoderLM):
    """Decoder-only LM whose mixers are named by `layer_types`, one entry a
    published layer, two sub-layers a layer: "conv" a double-gated short
    convolution (`GatedShortConv`, taps `conv_L_cache`, no bias),
    "full_attention" grouped-query softmax attention with plain-weight RMS
    norms on every head of q and k and rotary positions over the whole head
    (`rope_parameters["rope_theta"]`, half-split pairs). The feed-forward of
    published layer i (from 0) is a dense swiglu of `intermediate_size` for
    i < `num_dense_layers`, else sigmoid-routed swiglu experts chosen by
    score + a selection bias, renormalised over the chosen (+ 1e-6), with
    NO shared expert (the `lfm2_moe` shape). `num_hidden_layers` layers are
    BUILT, the published layers `layers_first` .. (a pipeline stage's
    share)."""

    num_hidden_layers: int = 4
    layers_first: int = 0
    norm_eps: float = 1e-5
    # which published layer mixes how; default: attention every fourth
    # layer from the third on
    layer_types: Optional[Sequence[str]] = None
    conv_L_cache: int = 3
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    rope_parameters: Optional[dict] = None
    # feed-forward
    num_dense_layers: int = 2
    intermediate_size: int = 512
    # routed experts
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 64
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True

    NORM = ("norm_eps", False)

    def sublayer_kinds(self):
        """The (mixer, feed-forward) kinds of the layers built."""
        held = range(self.layers_first, self.layers_first + self.num_hidden_layers)
        types = self.layer_types or [
            "full_attention" if i % 4 == 2 else "conv" for i in range(held.stop)]
        mixers = {"conv": "shortconv", "full_attention": "attention"}
        bad = sorted(set(types) - set(mixers))
        if bad or held.stop > len(types):
            raise ValueError(f"layer_types: {len(types)} entries for layers {held}, "
                             f"of them {bad} none of {sorted(mixers)}")
        return [(mixers[types[i]], "dense" if i < self.num_dense_layers else "experts")
                for i in held]

    def sublayers(self):
        sub = {
            "shortconv": lambda: GatedShortConv(conv_width=self.conv_L_cache),
            "attention": lambda: GatedAttention(
                n_heads=self.num_attention_heads, n_kv_heads=self.num_key_value_heads,
                head_dim=self.hidden_size // self.num_attention_heads, rotary_fraction=1.0,
                rope_theta=float((self.rope_parameters or {}).get("rope_theta", 1e6)),
                eps=self.norm_eps, gated=False, qk_norm=True, qk_norm_zero_centered=False),
            "dense": lambda: GatedMLP(width=self.intermediate_size, act="swiglu"),
            "experts": lambda: self._experts(
                top_k=self.num_experts_per_tok, expert_width=self.moe_intermediate_size,
                shared_width=0, norm_topk=self.norm_topk_prob, scoring="sigmoid",
                routed_scale=self.routed_scaling_factor, expert_act="swiglu",
                shared_gated=False, norm_eps=1e-6),
        }
        return [sub[kind]() for pair in self.sublayer_kinds() for kind in pair]


@dataclass
class WindowedMoELM(_DecoderLM):
    """Decoder-only LM whose attention layers are NOT alike: published layer
    i (from 0) is `layer_types[i]` — "sliding_attention", whose queries see
    the `sliding_window` keys up to and with their own, or "full_attention",
    which sees the whole past — with `num_attention_heads_per_layer[i]` query
    heads over `num_key_value_heads` key/value heads of `head_dim`, the
    rotary recipe of its type (`rope_parameters[type]`: `rope_theta`,
    `partial_rotary_factor` of the head turned, half-split pairs, and for
    `rope_type` "yarn" the frequency schedule and the factor on cos and sin
    of `hybrid.frequencies`), no q/k norm and ONE sigmoid gate a head and
    token on the head's output. Its feed-forward is a dense swiglu of
    `intermediate_size` for i in `mlp_only_layers`, else softmax-routed
    swiglu experts — top-k renormalised over the chosen, times
    `moe_routed_scaling_factor` — beside an ungated shared expert (the
    `laguna` shape). `num_hidden_layers` layers are BUILT, the published
    layers `layers_first` .. (a pipeline stage's share); both lists are
    indexed by the published layer. The head counts are the counts this
    rank HOLDS (a tensor-parallel rank's share of each layer's query and
    key/value heads, in the published ratio), as `num_experts` is.

    Each of the three extras can be absent (the `mellum` shape): `head_gate`
    off, `shared_expert_intermediate_size` 0, `mlp_only_layers` empty; one
    `num_attention_heads` then serves every layer in place of the list, and
    `moe_routed_scaling_factor` 1.0 scales nothing. `expert_exchange_axis`
    names the mesh axis whose ranks share every expert layer: all
    `num_experts` live split over it and the tokens go to their experts and
    back through `RoutedExperts`' exchange; on a mesh without the axis (or
    with one rank on it) the layers run as they do with every expert here."""

    num_hidden_layers: int = 4
    layers_first: int = 0
    rms_norm_eps: float = 1e-6
    # which published layer attends how, and over how many query heads;
    # default: a global layer of 4 heads, then three windowed ones of 6
    layer_types: Optional[Sequence[str]] = None
    num_attention_heads_per_layer: Optional[Sequence[int]] = None
    num_attention_heads: Optional[int] = None
    num_key_value_heads: int = 2
    head_dim: int = 64
    sliding_window: int = 16
    rope_parameters: Optional[dict] = None
    head_gate: bool = True
    # feed-forward
    mlp_only_layers: Sequence[int] = (0,)
    intermediate_size: int = 512
    # routed experts
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 64
    shared_expert_intermediate_size: int = 64
    moe_routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    expert_exchange_axis: Optional[str] = None

    #: a published layer type -> does it attend through the window
    KINDS = {"full_attention": False, "sliding_attention": True}

    def layers_built(self):
        """(published index, type, query heads) of the layers built."""
        held = range(self.layers_first, self.layers_first + self.num_hidden_layers)
        types = self.layer_types or [
            "sliding_attention" if i % 4 else "full_attention" for i in range(held.stop)]
        heads = self.num_attention_heads_per_layer or [
            self.num_attention_heads or (6 if self.KINDS.get(kind) else 4) for kind in types]
        bad = sorted(set(types) - set(self.KINDS))
        if bad or held.stop > min(len(types), len(heads)):
            raise ValueError(
                f"layer_types: {len(types)} entries and num_attention_heads_per_layer: "
                f"{len(heads)} for layers {held}; {bad} are none of {sorted(self.KINDS)} "
                f"in {list(types)}")
        return [(i, types[i], heads[i]) for i in held]

    def sublayers(self):
        recipes = self.rope_parameters or {}

        def attention(kind, n_heads):
            recipe = recipes.get(kind, {})
            return GatedAttention(
                n_heads=n_heads, n_kv_heads=self.num_key_value_heads, head_dim=self.head_dim,
                rotary_fraction=float(recipe.get("partial_rotary_factor", 1.0)),
                rope_theta=float(recipe.get("rope_theta", 10000.0)),
                rope_scaling=(dict(recipe) if recipe.get("rope_type", "default") != "default"
                              else None),
                eps=self.rms_norm_eps, gated=self.head_gate, gate="head", qk_norm=False,
                qk_norm_zero_centered=False,
                window=self.sliding_window if self.KINDS[kind] else None)

        def feed_forward(i):
            if i in tuple(self.mlp_only_layers):
                return GatedMLP(width=self.intermediate_size, act="swiglu")
            return self._experts(
                top_k=self.num_experts_per_tok, expert_width=self.moe_intermediate_size,
                shared_width=self.shared_expert_intermediate_size,
                norm_topk=self.norm_topk_prob, scoring="softmax",
                routed_scale=self.moe_routed_scaling_factor, expert_act="swiglu",
                shared_gated=False, norm_eps=1e-20, exchange_axis=self.expert_exchange_axis)

        return [sub for i, kind, n_heads in self.layers_built()
                for sub in (attention(kind, n_heads), feed_forward(i))]


@dataclass
class LoopLM(_DecoderLM):
    """Decoder-only LM whose layers run `total_ut_steps` times over the SAME
    weights (the `ouro` shape): `num_hidden_layers` layers of full multi-head
    attention with rotary positions over the whole head and a dense swiglu,
    each sub-layer between two RMS norms (the sandwich: y = x +
    rms(sub(rms(x)))), the final norm INSIDE the loop — its output is what
    the next pass reads and what the head reads. One `LoopedStack` holds
    them; `LoopExitOutput` reads the state of every pass with one head, a
    learned gate turns them into an exit distribution a token, and the score
    is the loss expected under it less `beta` times its entropy."""

    num_hidden_layers: int = 4
    num_attention_heads: int = 4
    num_key_value_heads: int = 4
    head_dim: int = 64
    intermediate_size: int = 512
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    total_ut_steps: int = 4
    beta: float = 0.1

    def sublayers(self):
        return [sub for _ in range(self.num_hidden_layers) for sub in (
            GatedAttention(
                n_heads=self.num_attention_heads, n_kv_heads=self.num_key_value_heads,
                head_dim=self.head_dim, rotary_fraction=1.0, rope_theta=float(self.rope_theta),
                eps=self.rms_norm_eps, gated=False, qk_norm=False, qk_norm_zero_centered=False),
            GatedMLP(width=self.intermediate_size, act="swiglu"))]

    def conf(self):
        return self.network([
            LoopedStack(layers=self.blocks(post_norm=True), steps=self.total_ut_steps),
            LoopExitOutput(n_out=self.vocab_size, loss="mcxent", activation="softmax",
                           has_bias=False, beta=self.beta),
        ])


@dataclass
class VisionTransformer(ZooModel):
    """ViT-style image classifier — net-new 14th zoo architecture (the
    reference zoo is pre-transformer). Patch embedding via a stride=patch
    conv, spatial positions become tokens (CnnToTokens), non-causal
    TransformerBlocks, mean-pooled head. Pure layer-library composition, so
    fit/output/serialization/transfer all apply."""

    num_classes: int = 10
    input_shape: Tuple[int, int, int] = (32, 32, 3)
    patch_size: int = 4
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 4

    def conf(self):
        from deeplearning4j_tpu.nn.layers import (
            PositionEmbedding,
            TransformerBlock,
        )
        from deeplearning4j_tpu.nn.preprocessors import CnnToTokens

        h, w, c = self.input_shape
        p = self.patch_size
        if h % p or w % p:
            raise ValueError(f"input {h}x{w} not divisible by patch {p}")
        n_tokens = (h // p) * (w // p)
        conf = NeuralNetConfiguration(
            seed=self.seed, updater=updaters.Adam(learning_rate=3e-4),
            weight_init="xavier",
        ).list([
            Conv2D(kernel_size=(p, p), stride=(p, p), n_out=self.d_model,
                   convolution_mode="truncate", activation="identity"),
            PositionEmbedding(max_len=n_tokens),
            *[TransformerBlock(n_heads=self.n_heads, causal=False)
              for _ in range(self.n_layers)],
            GlobalPooling(pooling_type="avg"),
            Output(n_out=self.num_classes, loss="mcxent"),
        ])
        conf.input_preprocessor(1, CnnToTokens())
        return conf.set_input_type(it.convolutional(h, w, c))


@dataclass
class TinyYOLO(ZooModel):
    """TinyYOLO backbone (zoo/model/TinyYOLO.java:254). Uses the Yolo2 output
    layer for detection loss."""

    num_classes: int = 20
    input_shape: Tuple[int, int, int] = (416, 416, 3)

    def conf(self):
        from deeplearning4j_tpu.nn.layers.objdetect import Yolo2Output

        h, w, c = self.input_shape

        def conv_unit(n_out):
            return [
                Conv2D(kernel_size=(3, 3), n_out=n_out, convolution_mode="same",
                       has_bias=False, activation="identity"),
                BatchNorm(activation="leakyrelu"),
            ]

        layers = []
        for i, ch in enumerate([16, 32, 64, 128, 256]):
            layers += conv_unit(ch)
            layers.append(Subsampling2D(kernel_size=(2, 2), stride=(2, 2)))
        layers += conv_unit(512)
        layers.append(Subsampling2D(kernel_size=(2, 2), stride=(1, 1),
                                    convolution_mode="same"))
        layers += conv_unit(1024)
        # detection head: 5 boxes * (5 + num_classes)
        layers.append(Conv2D(kernel_size=(1, 1),
                             n_out=5 * (5 + self.num_classes),
                             convolution_mode="same", activation="identity"))
        layers.append(Yolo2Output(
            boxes=[[1.08, 1.19], [3.42, 4.41], [6.63, 11.38],
                   [9.42, 5.11], [16.62, 10.52]],
            num_classes=self.num_classes,
        ))
        return NeuralNetConfiguration(
            seed=self.seed,
            updater=updaters.Adam(learning_rate=1e-3), l2=1e-4,
        ).list(layers).set_input_type(it.convolutional(h, w, c))


def _inception_module(g, name, inp, c1, c3r, c3, c5r, c5, pp):
    """GoogLeNet inception block (zoo/model/GoogLeNet.java helper)."""
    g.add_layer(f"{name}_1x1",
                Conv2D(kernel_size=(1, 1), n_out=c1, convolution_mode="same",
                       activation="relu"), inp)
    g.add_layer(f"{name}_3x3r",
                Conv2D(kernel_size=(1, 1), n_out=c3r, convolution_mode="same",
                       activation="relu"), inp)
    g.add_layer(f"{name}_3x3",
                Conv2D(kernel_size=(3, 3), n_out=c3, convolution_mode="same",
                       activation="relu"), f"{name}_3x3r")
    g.add_layer(f"{name}_5x5r",
                Conv2D(kernel_size=(1, 1), n_out=c5r, convolution_mode="same",
                       activation="relu"), inp)
    g.add_layer(f"{name}_5x5",
                Conv2D(kernel_size=(5, 5), n_out=c5, convolution_mode="same",
                       activation="relu"), f"{name}_5x5r")
    g.add_layer(f"{name}_pool",
                Subsampling2D(kernel_size=(3, 3), stride=(1, 1),
                              convolution_mode="same", pooling_type="max"), inp)
    g.add_layer(f"{name}_poolproj",
                Conv2D(kernel_size=(1, 1), n_out=pp, convolution_mode="same",
                       activation="relu"), f"{name}_pool")
    g.add_vertex(f"{name}_out", MergeVertex(),
                 f"{name}_1x1", f"{name}_3x3", f"{name}_5x5", f"{name}_poolproj")
    return f"{name}_out"


@dataclass
class GoogLeNet(ZooModel):
    """GoogLeNet / Inception-v1 (zoo/model/GoogLeNet.java:197)."""

    def conf(self):
        h, w, c = self.input_shape
        g = NeuralNetConfiguration(
            seed=self.seed,
            updater=updaters.Nesterovs(learning_rate=1e-2, momentum=0.9),
            l2=2e-4,
        ).graph().add_inputs("in")
        g.add_layer("stem1", Conv2D(kernel_size=(7, 7), stride=(2, 2), n_out=64,
                                    convolution_mode="same", activation="relu"),
                    "in")
        g.add_layer("pool1", Subsampling2D(kernel_size=(3, 3), stride=(2, 2),
                                           convolution_mode="same"), "stem1")
        g.add_layer("lrn1", LRN(), "pool1")
        g.add_layer("stem2", Conv2D(kernel_size=(1, 1), n_out=64,
                                    convolution_mode="same", activation="relu"),
                    "lrn1")
        g.add_layer("stem3", Conv2D(kernel_size=(3, 3), n_out=192,
                                    convolution_mode="same", activation="relu"),
                    "stem2")
        g.add_layer("lrn2", LRN(), "stem3")
        g.add_layer("pool2", Subsampling2D(kernel_size=(3, 3), stride=(2, 2),
                                           convolution_mode="same"), "lrn2")
        x = _inception_module(g, "i3a", "pool2", 64, 96, 128, 16, 32, 32)
        x = _inception_module(g, "i3b", x, 128, 128, 192, 32, 96, 64)
        g.add_layer("pool3", Subsampling2D(kernel_size=(3, 3), stride=(2, 2),
                                           convolution_mode="same"), x)
        x = _inception_module(g, "i4a", "pool3", 192, 96, 208, 16, 48, 64)
        x = _inception_module(g, "i4b", x, 160, 112, 224, 24, 64, 64)
        x = _inception_module(g, "i4c", x, 128, 128, 256, 24, 64, 64)
        x = _inception_module(g, "i4d", x, 112, 144, 288, 32, 64, 64)
        x = _inception_module(g, "i4e", x, 256, 160, 320, 32, 128, 128)
        g.add_layer("pool4", Subsampling2D(kernel_size=(3, 3), stride=(2, 2),
                                           convolution_mode="same"), x)
        x = _inception_module(g, "i5a", "pool4", 256, 160, 320, 32, 128, 128)
        x = _inception_module(g, "i5b", x, 384, 192, 384, 48, 128, 128)
        g.add_layer("avgpool", GlobalPooling(pooling_type="avg"), x)
        g.add_layer("dropout", DropoutLayer(dropout=0.4), "avgpool")
        g.add_layer("out", Output(n_out=self.num_classes, loss="mcxent"),
                    "dropout")
        g.set_outputs("out")
        g.set_input_types(it.convolutional(h, w, c))
        return g


@dataclass
class InceptionResNetV1(ZooModel):
    """Inception-ResNet v1 (zoo/model/InceptionResNetV1.java:324) — compact
    rendition: stem + N inception-resnet-A blocks with residual adds."""

    num_classes: int = 128  # embedding net by default (facenet use)

    def conf(self):
        h, w, c = self.input_shape
        g = NeuralNetConfiguration(
            seed=self.seed, updater=updaters.RmsProp(learning_rate=1e-1),
        ).graph().add_inputs("in")

        def conv(name, inp, k, n, stride=(1, 1)):
            g.add_layer(name, Conv2D(kernel_size=k, stride=stride, n_out=n,
                                     convolution_mode="same",
                                     activation="relu"), inp)
            return name

        x = conv("stem1", "in", (3, 3), 32, (2, 2))
        x = conv("stem2", x, (3, 3), 32)
        x = conv("stem3", x, (3, 3), 64)
        g.add_layer("stem_pool", Subsampling2D(kernel_size=(3, 3),
                                               stride=(2, 2),
                                               convolution_mode="same"), x)
        x = conv("stem4", "stem_pool", (1, 1), 80)
        x = conv("stem5", x, (3, 3), 192)
        x = conv("stem6", x, (3, 3), 256, (2, 2))

        for i in range(5):
            inp = x
            b0 = conv(f"ira{i}_b0", inp, (1, 1), 32)
            b1 = conv(f"ira{i}_b1a", inp, (1, 1), 32)
            b1 = conv(f"ira{i}_b1b", b1, (3, 3), 32)
            b2 = conv(f"ira{i}_b2a", inp, (1, 1), 32)
            b2 = conv(f"ira{i}_b2b", b2, (3, 3), 32)
            b2 = conv(f"ira{i}_b2c", b2, (3, 3), 32)
            g.add_vertex(f"ira{i}_cat", MergeVertex(), b0, b1, b2)
            g.add_layer(f"ira{i}_up",
                        Conv2D(kernel_size=(1, 1), n_out=256,
                               convolution_mode="same",
                               activation="identity"), f"ira{i}_cat")
            g.add_vertex(f"ira{i}_add", ElementWiseVertex(op="add"),
                         inp, f"ira{i}_up")
            g.add_layer(f"ira{i}_act", Activation(activation="relu"),
                        f"ira{i}_add")
            x = f"ira{i}_act"

        g.add_layer("avgpool", GlobalPooling(pooling_type="avg"), x)
        g.add_layer("bottleneck", Dense(n_out=self.num_classes,
                                        activation="identity"), "avgpool")
        g.add_vertex("embeddings", L2NormalizeVertex(), "bottleneck")
        g.add_layer("out", Output(n_out=self.num_classes, loss="mcxent"),
                    "embeddings")
        g.set_outputs("out")
        g.set_input_types(it.convolutional(h, w, c))
        return g


@dataclass
class FaceNetNN4Small2(ZooModel):
    """NN4.small2 face-embedding net (zoo/model/FaceNetNN4Small2.java:362) —
    inception-style trunk to an L2-normalized embedding + center-loss output."""

    num_classes: int = 1000
    embedding_size: int = 128
    input_shape: Tuple[int, int, int] = (96, 96, 3)

    def conf(self):
        from deeplearning4j_tpu.nn.layers import CenterLossOutput

        h, w, c = self.input_shape
        g = NeuralNetConfiguration(
            seed=self.seed, updater=updaters.Adam(learning_rate=1e-3),
        ).graph().add_inputs("in")
        g.add_layer("stem1", Conv2D(kernel_size=(7, 7), stride=(2, 2),
                                    n_out=64, convolution_mode="same",
                                    activation="relu"), "in")
        g.add_layer("pool1", Subsampling2D(kernel_size=(3, 3), stride=(2, 2),
                                           convolution_mode="same"), "stem1")
        g.add_layer("lrn1", LRN(), "pool1")
        g.add_layer("i2", Conv2D(kernel_size=(1, 1), n_out=64,
                                 convolution_mode="same", activation="relu"),
                    "lrn1")
        g.add_layer("i3", Conv2D(kernel_size=(3, 3), n_out=192,
                                 convolution_mode="same", activation="relu"),
                    "i2")
        g.add_layer("lrn2", LRN(), "i3")
        g.add_layer("pool2", Subsampling2D(kernel_size=(3, 3), stride=(2, 2),
                                           convolution_mode="same"), "lrn2")
        x = _inception_module(g, "f3a", "pool2", 64, 96, 128, 16, 32, 32)
        x = _inception_module(g, "f3b", x, 64, 96, 128, 32, 64, 64)
        g.add_layer("pool3", Subsampling2D(kernel_size=(3, 3), stride=(2, 2),
                                           convolution_mode="same"), x)
        x = _inception_module(g, "f4a", "pool3", 256, 96, 192, 32, 64, 128)
        x = _inception_module(g, "f5a", x, 256, 96, 384, 16, 64, 96)
        g.add_layer("avgpool", GlobalPooling(pooling_type="avg"), x)
        g.add_layer("bottleneck", Dense(n_out=self.embedding_size,
                                        activation="identity"), "avgpool")
        g.add_vertex("embeddings", L2NormalizeVertex(), "bottleneck")
        g.add_layer("out", CenterLossOutput(n_out=self.num_classes,
                                            loss="mcxent", alpha=0.9,
                                            lambda_=2e-4), "embeddings")
        g.set_outputs("out")
        g.set_input_types(it.convolutional(h, w, c))
        return g
