"""Multi-host (multi-controller) training — one process per host.

Run on each host of a real pod slice (or locally, see below):

    JAX_COORDINATOR_ADDRESS=host0:12345 JAX_NUM_PROCESSES=2 \
    JAX_PROCESS_ID=<rank> python examples/multihost_training.py

Every process runs this SAME program: it joins the coordinator, builds
the global mesh, trains with a SharedTrainingMaster (one SPMD step per
batch, gradients psum'd by XLA), and finishes with a collectively merged
evaluation. See docs/PARALLELISM.md for the design.

With no coordinator env set, the script demonstrates the full thing
LOCALLY by relaunching itself as 2 processes x 4 virtual CPU devices.
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def demo_relaunch():
    import socket
    import subprocess

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        # one process per chip: children of a local demo are pinned to
        # the CPU so they never contend for the parent's accelerator
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "JAX_NUM_PROCESSES": "2",
            "JAX_PROCESS_ID": str(rank),
        })
        procs.append(subprocess.Popen([sys.executable, __file__], env=env))
    rc = []
    for p in procs:
        try:
            rc.append(p.wait(timeout=300))
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            sys.exit("demo timed out (collective deadlock?)")
    # signal deaths have negative returncodes — any nonzero is a failure
    sys.exit(next((r for r in rc if r != 0), 0))


def main():
    if "JAX_COORDINATOR_ADDRESS" not in os.environ:
        print("(no coordinator configured — demoing locally as "
              "2 processes x 4 virtual CPU devices)")
        demo_relaunch()
        return

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.distributed import (
        SharedTrainingMaster,
        evaluate_across_processes,
        initialize,
        runtime_info,
    )
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn import inputs as it
    from deeplearning4j_tpu.nn import updaters
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import Dense, Output

    initialize()  # reads JAX_COORDINATOR_ADDRESS / _NUM_PROCESSES / _ID
    rt = runtime_info()
    print(f"[rank {rt.process_index}] {rt.local_device_count} local / "
          f"{rt.global_device_count} global devices")

    conf = NeuralNetConfiguration(
        seed=7, updater=updaters.Adam(5e-3),
    ).list([
        Dense(n_out=32, activation="relu"),
        Output(n_out=3, loss="mcxent"),
    ]).set_input_type(it.feed_forward(8))
    net = MultiLayerNetwork(conf).init()

    # every process feeds the same global batches (same seed); the mesh
    # scatters each host's addressable shard
    rng = np.random.default_rng(0)
    x = rng.standard_normal((256, 8)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 256)]

    master = SharedTrainingMaster(mesh=rt.global_mesh())
    master.execute_training(net, ListDataSetIterator(DataSet(x, y),
                                                     batch=64), epochs=3)

    # each process evaluates ITS shard; results merge collectively
    per = len(x) // rt.process_count
    lo = rt.process_index * per
    ev = evaluate_across_processes(
        net, ListDataSetIterator(DataSet(x[lo:lo + per], y[lo:lo + per]),
                                 batch=64))
    print(f"[rank {rt.process_index}] score={net.score_:.4f} "
          f"merged-eval accuracy={ev.accuracy():.3f}")


if __name__ == "__main__":
    main()
