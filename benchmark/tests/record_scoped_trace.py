#!/usr/bin/env python3
"""Record the small SCOPED TPU traces that the CPU tests read. Run on the
chip; writes chiprun_out/.

    python3 benchmark/tests/record_scoped_trace.py            # scoped_tpu.xplane.pb
    python3 benchmark/tests/record_scoped_trace.py hybrid     # scoped_hybrid_tpu.xplane.pb

The first (PR 35; test_scope_reduce.py): three train steps of a two-block
`zoo.TransformerLM` (t 512, heads of 64, integer labels) through the
program's own `fit` — each block a checkpoint (remat 'full'), the flash
kernel pair under `attend`, the head + loss a loop over row blocks under
`dl4j.loss`, Adam under `dl4j.update`.

The second (PR 51; test_scoped_readers.py): three train steps of a
two-layer `zoo.HybridMoELM` (t 512, 2 rows) — a gated-delta-rule mixer whose
chunk rule is the `dl4j_gdn_*` kernel pair under `rule`, its core mapped over
the rows (a `while` a pass), then gated attention, each followed by routed
experts whose grouped products libtpu strips of their stack
(`ragged-dot-none`), every block a checkpoint, the row-blocked head making
its gradient in the forward visit (`dl4j.loss/grad`).

Both captured with the host tracer off, as a cell's device-only capture is.
"""
import glob
import os
import shutil
import sys
import tempfile

import jax
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def transformer():
    from deeplearning4j_tpu import zoo

    return (zoo.TransformerLM(num_classes=4096, max_length=512, d_model=128, n_heads=2,
                              n_layers=2, remat="full").init(), 4, 4096, "scoped_tpu")


def hybrid():
    from deeplearning4j_tpu import zoo

    net = zoo.HybridMoELM(
        vocab_size=4096, hidden_size=256, num_hidden_layers=2, full_attention_interval=2,
        max_length=512, num_attention_heads=2, num_key_value_heads=1, head_dim=128,
        linear_num_key_heads=4, linear_num_value_heads=8, linear_key_head_dim=128,
        linear_value_head_dim=128, num_experts=4, num_experts_published=8, experts_first=0,
        num_experts_per_tok=2, moe_intermediate_size=256, shared_expert_intermediate_size=256,
        capacity_factor=2.0, remat="full").init()
    return net, 2, 4096, "scoped_hybrid_tpu"


def main(which):
    assert jax.devices()[0].platform == "tpu"
    from deeplearning4j_tpu import dtypes
    from deeplearning4j_tpu.datasets.dataset import DataSet

    dtypes.set_mixed_precision(True)
    net, rows, vocab, out = {"transformer": transformer, "hybrid": hybrid}[which]()
    ids = np.random.default_rng(0).integers(0, vocab, (rows, 512)).astype(np.int32)
    ds = DataSet(ids, np.roll(ids, -1, 1).astype(np.int32))
    net.fit(ds)                                   # compiles
    d = tempfile.mkdtemp(prefix="scoped_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    for _ in range(3):
        net.fit(ds)
    jax.block_until_ready(net.params)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))[0]
    os.makedirs("chiprun_out", exist_ok=True)
    shutil.copy(src, f"chiprun_out/{out}.xplane.pb")
    print("wrote", os.path.getsize(src), "bytes; loss", net.score_)


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "transformer")
