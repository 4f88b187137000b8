"""One entry function per kernel family for the layers: `attention.attend`,
`linear.grouped_dot` (XLA's grouped product at widths it runs well), and
these three, which return None where the caller keeps its XLA form."""
from deeplearning4j_tpu.ops.pallas_kernels import fused_affine_act, fused_lstm  # noqa: F401
from deeplearning4j_tpu.ops.xent_kernel import fused_linear_xent  # noqa: F401
