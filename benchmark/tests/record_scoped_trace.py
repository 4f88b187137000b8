#!/usr/bin/env python3
"""Record the small SCOPED TPU trace that test_scope_reduce.py reads: three
train steps of a two-block `zoo.TransformerLM` (t 512, heads of 64, integer
labels) through the program's own `fit` — each block a checkpoint (remat
'full'), the flash kernel pair under `attend`, the head + loss a loop over
row blocks under `dl4j.loss`, Adam under `dl4j.update` — captured with the
host tracer off, as a cell's device-only capture is. Run on the chip; writes
chiprun_out/.

    python3 benchmark/tests/record_scoped_trace.py
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


def main():
    assert jax.devices()[0].platform == "tpu"
    from deeplearning4j_tpu import dtypes, zoo
    from deeplearning4j_tpu.datasets.dataset import DataSet

    dtypes.set_mixed_precision(True)
    net = zoo.TransformerLM(num_classes=4096, max_length=512, d_model=128, n_heads=2,
                            n_layers=2, remat="full").init()
    ids = np.random.default_rng(0).integers(0, 4096, (4, 512)).astype(np.int32)
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
    shutil.copy(src, "chiprun_out/scoped_tpu.xplane.pb")
    print("wrote", os.path.getsize(src), "bytes; loss", net.score_)


if __name__ == "__main__":
    main()
