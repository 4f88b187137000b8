from deeplearning4j_tpu.parallel.mesh import MeshSpec, build_mesh  # noqa: F401
from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper  # noqa: F401
from deeplearning4j_tpu.parallel.inference import ParallelInference  # noqa: F401
from deeplearning4j_tpu.ops.ring import (  # noqa: F401
    ring_attention,
    ring_attention_sharded,
    sequence_parallel,
)
