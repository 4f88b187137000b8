#!/usr/bin/env python3
"""Record the small TPU trace that test_trace_reduce.py reads: three runs of
one jitted program (two matmuls and a reduction) with a host annotation and
a pause between them, so that busy time, gaps, per-name sums and host spans
all have something to find. Run on the chip; writes chiprun_out/.

    python3 benchmark/tests/record_small_trace.py
"""
import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp


def main():
    assert jax.devices()[0].platform == "tpu"

    @jax.jit
    def small_step(a, b):
        return jnp.tanh(a @ b).sum() + (b @ a).mean()

    a = jnp.ones((512, 512), jnp.bfloat16)
    small_step(a, a).block_until_ready()
    d = tempfile.mkdtemp(prefix="small_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.pause"):
            time.sleep(0.01)
        small_step(a, a).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))[0]
    os.makedirs("chiprun_out", exist_ok=True)
    shutil.copy(src, "chiprun_out/small_tpu.xplane.pb")
    print("wrote", os.path.getsize(src), "bytes")


if __name__ == "__main__":
    main()
