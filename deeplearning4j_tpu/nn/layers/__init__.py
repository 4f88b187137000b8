"""Layer library — importing this module populates the layer registry.

Inventory parity target: the 41 config classes of nn/conf/layers/ (SURVEY.md
§2.1 'Layer configs' row).
"""
from deeplearning4j_tpu.nn.layers.base import Layer, layer_types, register_layer  # noqa: F401
from deeplearning4j_tpu.nn.layers.dense import (  # noqa: F401
    Activation,
    Dense,
    DropoutLayer,
    ElementWiseMultiplication,
    Embedding,
    EmbeddingSequence,
)
from deeplearning4j_tpu.nn.layers.output import (  # noqa: F401
    BaseOutputLayer,
    CenterLossOutput,
    LoopExitOutput,
    LossLayer,
    Output,
    RnnOutput,
)
from deeplearning4j_tpu.nn.layers.convolution import (  # noqa: F401
    Conv1D,
    Conv2D,
    Deconv2D,
    SeparableConv2D,
    Subsampling1D,
    Subsampling2D,
    Upsampling1D,
    Upsampling2D,
    ZeroPadding1D,
    ZeroPadding2D,
)
from deeplearning4j_tpu.nn.layers.normalization import LRN, BatchNorm  # noqa: F401
from deeplearning4j_tpu.nn.layers.pooling import GlobalPooling  # noqa: F401
from deeplearning4j_tpu.nn.layers.recurrent import (  # noqa: F401
    LSTM,
    BaseRecurrent,
    GravesBidirectionalLSTM,
    GravesLSTM,
    LastTimeStep,
    SimpleRnn,
)
from deeplearning4j_tpu.nn.layers.autoencoder import (  # noqa: F401
    RBM,
    AutoEncoder,
    VariationalAutoencoder,
)
from deeplearning4j_tpu.nn.layers.misc import Frozen  # noqa: F401
from deeplearning4j_tpu.nn.layers.attention import (  # noqa: F401
    LayerNorm,
    MultiHeadAttention,
    PositionEmbedding,
    TransformerBlock,
)
from deeplearning4j_tpu.nn.layers.blocks import (  # noqa: F401
    HybridBlock,
    LoopedStack,
    SubLayerBlock,
)
from deeplearning4j_tpu.nn.layers.hybrid import (  # noqa: F401
    GatedAttention,
    GatedDeltaNet,
    GatedMLP,
    GatedShortConv,
    KimiDeltaAttention,
    LatentAttention,
    RMSNorm,
    RoutedExperts,
)
from deeplearning4j_tpu.nn.layers.ssm import Mamba2Mixer  # noqa: F401
from deeplearning4j_tpu.nn.layers.objdetect import Yolo2Output  # noqa: F401
