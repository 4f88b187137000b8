"""deeplearning4j_tpu — a TPU-native deep-learning framework.

A ground-up re-design of the capabilities of Deeplearning4j (reference:
/root/reference, Maven 0.9.2-SNAPSHOT) for TPU hardware: the declarative
layer-config DSL compiles to single jitted XLA programs (jax/pjit/pallas)
instead of hand-written JVM backprop; distributed training runs over
`jax.sharding.Mesh` ICI/DCN collectives instead of ParallelWrapper threads and
the Aeron parameter server.

Top-level layout (mirrors SURVEY.md §1 layer map):
    nn/         config DSL, layers, activations/losses/initializers/updaters
    models/     MultiLayerNetwork & ComputationGraph runtimes + serialization
    optimize/   solvers (training drivers) + listener SPI
    eval/       Evaluation / ROC / regression metrics
    datasets/   DataSet containers + iterator framework (async prefetch)
    parallel/   device meshes, data/tensor parallel training, ParallelInference
    ops/        pallas TPU kernels for hot paths
    zoo/        model zoo (LeNet ... ResNet50/VGG/Inception/YOLO)
    modelimport/ Keras h5 import
    resilience/ fault-tolerant training runtime (atomic checkpoint/resume,
                divergence sentry, retry/backoff, chaos injection)
    earlystopping/, nlp/, graphembed/, knn/, ui/, util/
"""

import time as _time

_t_import0 = _time.perf_counter()  # before anything of the package loads

__version__ = "0.1.0"

from deeplearning4j_tpu.nn import conf  # noqa: E402,F401
from deeplearning4j_tpu.analysis import analyze  # noqa: E402,F401
from deeplearning4j_tpu.telemetry import trace as _trace  # noqa: E402

# the `import` row of the phase account (telemetry.setup_log()["import_s"])
_trace.record_import(_t_import0, _time.perf_counter())
