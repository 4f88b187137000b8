"""Shared training engine (ROADMAP open item 1 down payment).

`training.engine` owns the inner fit loop for all three fit paths
(MultiLayerNetwork, ComputationGraph, ParallelWrapper): batch staging,
the windowed device-resident K-step dispatch (`DL4J_TPU_STEP_WINDOW`),
and the per-step listener/score bookkeeping — one seam instead of three
hand-copied loops (docs/PERFORMANCE.md).
"""
from deeplearning4j_tpu.training.engine import (  # noqa: F401
    WindowedFitLoop,
    build_window_scan,
    window_size,
)
