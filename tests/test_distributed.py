"""Distributed orchestration: TrainingMaster SPI, phase stats, elastic
checkpoint/resume. In-process workers play the executors, the same stand-in
the reference's Spark tests use (`local[N]`, BaseSparkTest.java:89)."""
import json
import os

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.distributed import (
    CheckpointManager,
    ElasticTrainer,
    ParameterAveragingTrainingMaster,
    SharedTrainingMaster,
    TrainingStats,
    runtime_info,
)
from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn import updaters
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import Dense, Output


def _net(seed=1):
    conf = NeuralNetConfiguration(
        seed=seed, updater=updaters.Adam(learning_rate=5e-3),
    ).list([
        Dense(n_out=16, activation="relu"),
        Output(n_out=3, loss="mcxent"),
    ]).set_input_type(it.feed_forward(4))
    return MultiLayerNetwork(conf).init()


def test_runtime_info_single_process():
    rt = runtime_info()
    assert rt.process_count == 1 and rt.is_coordinator
    assert rt.global_device_count >= 1
    mesh = rt.global_mesh()
    assert mesh.shape["data"] == rt.global_device_count


class TestParameterAveraging:
    def test_trains_and_records_stats(self, iris_like):
        net = _net()
        master = ParameterAveragingTrainingMaster(
            num_workers=4, batches_per_worker=2)
        it_ = ListDataSetIterator(iris_like, batch=10)
        s0 = None
        for _ in range(8):
            master.execute_training(net, it_)
            s0 = s0 if s0 is not None else net.score_
        assert net.score_ < s0
        keys = master.stats.keys()
        for k in ("split", "broadcast", "fit", "fit_all", "aggregate"):
            assert k in keys, keys
        # per-worker fit events exist
        workers = {e.worker for e in master.stats.events if e.key == "fit"}
        assert len(workers) >= 2

    def test_stats_export(self, tmp_path, iris_like):
        net = _net()
        master = ParameterAveragingTrainingMaster(num_workers=2)
        master.execute_training(net, ListDataSetIterator(iris_like, batch=25))
        j = tmp_path / "stats.json"
        h = tmp_path / "stats.html"
        master.stats.export_json(str(j))
        master.stats.export_html(str(h))
        data = json.loads(j.read_text())
        assert data["totals_ms"]["fit"] > 0
        assert "<html" in h.read_text()
        assert master.stats.summary().startswith("phase")

    def test_fit_trace_merges_workers_under_split_span(self, iris_like,
                                                       monkeypatch):
        """ISSUE 10 acceptance: a 2-worker fit produces ONE trace — the
        master's `split.dispatch` spans and the workers' `fit` EventStats
        (merged via merge_training_stats) share the fit-level trace_id,
        and each worker fit parents to the split span it ran under,
        across the executor-thread handoff."""
        from deeplearning4j_tpu.telemetry import trace as trace_mod

        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "1")
        trace_mod.configure(enabled=True)
        try:
            net = _net()
            master = ParameterAveragingTrainingMaster(num_workers=2)
            master.execute_training(
                net, ListDataSetIterator(iris_like, batch=25))
            tr = trace_mod.tracer()
            assert tr.merge_training_stats(master.stats) > 0
            evs = tr.to_chrome_trace()["traceEvents"]
            splits = [e for e in evs if e["name"] == "split.dispatch"]
            assert splits
            tid = splits[0]["args"]["trace_id"]
            # every split dispatch of this fit rides the same trace
            assert all(e["args"]["trace_id"] == tid for e in splits)
            split_ids = {e["args"]["span_id"] for e in splits}
            fits = [e for e in evs if e["name"] == "fit"
                    and (e.get("args") or {}).get("trace_id") == tid]
            assert fits
            # worker fit spans parent to the master's split span even
            # though they were recorded on executor threads (the
            # explicit attach/detach handoff in master._run_split)
            assert all(e["args"]["parent_id"] in split_ids for e in fits)
            # merged worker events land on their own labelled lanes,
            # distinct from the master's live span lane
            assert {e["tid"] for e in fits}.isdisjoint(
                {e["tid"] for e in splits})
        finally:
            trace_mod.configure(enabled=None)

    def test_worker_exception_surfaces(self, iris_like):
        net = _net()
        master = ParameterAveragingTrainingMaster(num_workers=2)
        bad = ListDataSetIterator(iris_like, batch=10)

        class Boom(Exception):
            pass

        orig = net.clone

        def bad_clone():
            m = orig()

            def explode(ds):
                raise Boom()

            m._dispatch_step = explode
            return m

        net.clone = bad_clone
        with pytest.raises(Boom):
            master.execute_training(net, bad)


class TestSharedTraining:
    def test_trains_via_mesh(self, iris_like):
        net = _net()
        master = SharedTrainingMaster()
        s0 = None
        for _ in range(5):
            master.execute_training(net, ListDataSetIterator(iris_like,
                                                             batch=24))
            s0 = s0 if s0 is not None else net.score_
        assert np.isfinite(net.score_)
        assert net.score_ < s0


class TestCompressedStreaming:
    def test_compressed_epoch_consumes_iterator_lazily(self):
        """The threshold-compressed path must STREAM batches — one pulled
        per collective round — not materialize the epoch up front the way
        the old list(iterator) did (the reference streams RDD splits,
        ParameterAveragingTrainingMaster.java:308). Pinned by producing
        batch i only after the model has already trained on 0..i-1."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.distributed import SharedTrainingMaster

        net = _net()
        rng = np.random.default_rng(11)
        n_batches = 4
        iteration_at_produce = []

        class LazyIter:
            def __iter__(self):
                for _ in range(n_batches):
                    # an eager list(iterator) would record iteration==0
                    # for every batch; streaming records 0,1,2,...
                    iteration_at_produce.append(net.iteration)
                    x = rng.standard_normal((8, 4)).astype(np.float32)
                    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)]
                    yield DataSet(x, y)

        master = SharedTrainingMaster(compression_threshold=1e-3)
        # drive the compressed epoch directly: execute_training only takes
        # this path multi-process, but the collective degrades to a
        # 1-process allgather so the epoch logic runs unchanged
        master._compressed_epoch(net, LazyIter(), master._stats())
        assert iteration_at_produce == list(range(n_batches))
        assert net.iteration == n_batches
        assert np.isfinite(net.score_)


class TestElastic:
    def test_checkpoint_rotation_and_restore(self, tmp_path, iris_like):
        net = _net()
        cm = CheckpointManager(str(tmp_path), keep=2)
        for step in (1, 2, 3):
            net.fit(iris_like.features, iris_like.labels)
            cm.save(net, step)
        assert cm.list_steps() == [2, 3]  # rotated
        restored, meta = cm.restore_latest()
        assert meta["step"] == 3
        np.testing.assert_allclose(
            restored.output(iris_like.features[:5]),
            net.output(iris_like.features[:5]), atol=1e-6)

    def test_restore_skips_corrupt_newest(self, tmp_path, iris_like):
        net = _net()
        cm = CheckpointManager(str(tmp_path), keep=3)
        net.fit(iris_like.features, iris_like.labels)
        cm.save(net, 1)
        # corrupt "newer" checkpoint
        (tmp_path / "checkpoint_00000002.zip").write_bytes(b"not a zip")
        restored, meta = cm.restore_latest()
        assert restored is not None and meta["step"] == 1

    def test_elastic_resume(self, tmp_path, iris_like):
        it_ = ListDataSetIterator(iris_like, batch=15)
        net = _net()
        master = ParameterAveragingTrainingMaster(num_workers=2)
        trainer = ElasticTrainer(master, str(tmp_path), checkpoint_every=1)
        trainer.fit(net, it_, epochs=2)
        it_count = net.iteration
        assert it_count > 0 and len(trainer.ckpt.list_steps()) > 0

        # simulated preemption: fresh process, fresh model object
        net2 = _net(seed=99)
        master2 = ParameterAveragingTrainingMaster(num_workers=2)
        trainer2 = ElasticTrainer(master2, str(tmp_path), checkpoint_every=1)
        assert trainer2.resume_into(net2)
        assert net2.iteration == it_count
        np.testing.assert_allclose(net2.output(iris_like.features[:5]),
                                   net.output(iris_like.features[:5]),
                                   atol=1e-6)


def test_multiprocess_runtime_two_controllers():
    """REAL multi-process jax.distributed smoke test: 2 coordinator-
    connected processes x 4 virtual CPU devices each. Builds the global
    8-device mesh through distributed/runtime.py, runs one cross-process
    ParameterAveraging epoch and one shared-gradients SPMD epoch, and
    checks both processes converge on identical params (the
    SharedTrainingWrapper.java:160-244 role, without compile-only
    confidence)."""
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    here = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(here, "dist_worker.py")
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",  # a child never shares the parent's chip
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "JAX_NUM_PROCESSES": "2",
            "JAX_PROCESS_ID": str(rank),
        })
        procs.append(subprocess.Popen(
            [sys.executable, worker], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = []
    for rank, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(f"rank {rank} timed out (collective deadlock?)")
        assert p.returncode == 0, f"rank {rank} failed:\n{out}\n{err}"
        outs.append(out)
    oks = [l for o in outs for l in o.splitlines() if l.startswith("DIST_OK")]
    assert len(oks) == 2, outs
    # both ranks report the same averaged checksums
    assert oks[0].split("avg=")[1] == oks[1].split("avg=")[1], oks


def test_evaluate_shards_merges_like_single_pass():
    """Per-shard threaded evaluation merged == one sequential evaluation
    (the SparkDl4jMultiLayer.evaluate per-partition merge)."""
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.distributed import evaluate_shards

    net = _net()
    rng = np.random.default_rng(3)
    x = rng.standard_normal((96, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 96)]
    net.fit(ListDataSetIterator(DataSet(x, y), batch=32), epochs=10)

    shards = [ListDataSetIterator(DataSet(x[i::3], y[i::3]), batch=16)
              for i in range(3)]
    merged = evaluate_shards(net, shards)
    single = net.evaluate(ListDataSetIterator(DataSet(x, y), batch=32))
    assert merged.accuracy() == single.accuracy()
    assert int(merged.confusion.matrix.sum()) == 96

    # fill-in-place contract: the passed evaluator is the one filled
    from deeplearning4j_tpu.eval.evaluation import Evaluation
    mine = Evaluation()
    shards2 = [ListDataSetIterator(DataSet(x[i::3], y[i::3]), batch=16)
               for i in range(3)]
    ret = evaluate_shards(net, shards2, evaluation=mine)
    assert ret is mine
    assert int(mine.confusion.matrix.sum()) == 96
    assert mine.accuracy() == single.accuracy()


def test_evaluate_shards_rejects_used_evaluator():
    import numpy as np
    import pytest

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.distributed import evaluate_shards
    from deeplearning4j_tpu.eval.evaluation import Evaluation

    net = _net()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((16, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
    used = Evaluation()
    used.eval(y, np.asarray(net.output(x)))
    with pytest.raises(ValueError, match="fresh evaluator"):
        evaluate_shards(net, [ListDataSetIterator(DataSet(x, y), batch=8)],
                        evaluation=used)

    # the is_empty() protocol covers every IEvaluation, not just the
    # classification confusion special-case: a previously-filled ROC
    # prototype is rejected too (it would be double-counted otherwise)
    from deeplearning4j_tpu.eval.roc import ROC

    used_roc = ROC()
    used_roc.eval(y[:, :2], np.asarray(net.output(x))[:, :2])
    with pytest.raises(ValueError, match="fresh evaluator"):
        evaluate_shards(net, [ListDataSetIterator(DataSet(x, y), batch=8)],
                        evaluation=used_roc,
                        output_fn=lambda a: np.asarray(net.output(a))[:, :2])


def test_ievaluation_is_empty_protocol():
    import numpy as np

    from deeplearning4j_tpu.eval.binary import EvaluationBinary
    from deeplearning4j_tpu.eval.calibration import EvaluationCalibration
    from deeplearning4j_tpu.eval.evaluation import Evaluation
    from deeplearning4j_tpu.eval.regression import RegressionEvaluation
    from deeplearning4j_tpu.eval.roc import ROC, ROCBinary, ROCMultiClass

    protos = [Evaluation(), EvaluationBinary(), RegressionEvaluation(),
              EvaluationCalibration(), ROC(), ROCMultiClass(), ROCBinary()]
    for p in protos:
        assert p.is_empty(), type(p).__name__
    y = np.eye(2, dtype=np.float32)[[0, 1, 1, 0]]
    p_hat = np.asarray([[.8, .2], [.3, .7], [.4, .6], [.9, .1]], np.float32)
    for p in protos:
        p.eval(y, p_hat)
        assert not p.is_empty(), type(p).__name__
