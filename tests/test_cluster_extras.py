"""Cluster extras (SURVEY §2.4 spark-module equivalents): data export/
repartition, distributed early stopping, distributed word2vec, streaming
serving, ML-pipeline estimator."""
import numpy as np
import pytest

from deeplearning4j_tpu.datasets import DataSet, ListDataSetIterator
from deeplearning4j_tpu.distributed.data import (
    FileShardDataSetIterator,
    RebatchingDataSetIterator,
    batch_and_export,
    export_dataset_batches,
    split_for_workers,
)
from deeplearning4j_tpu.distributed.earlystopping import (
    DistributedEarlyStoppingTrainer,
)
from deeplearning4j_tpu.distributed.master import (
    ParameterAveragingTrainingMaster,
)
from deeplearning4j_tpu.distributed.pipeline import NetworkEstimator
from deeplearning4j_tpu.distributed.streaming import (
    StreamingInferencePipeline,
    Topic,
)
from deeplearning4j_tpu.distributed.word2vec import (
    DistributedWord2Vec,
    TextPipeline,
)
from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn import updaters
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import Dense, Output


def _ds(n=120, f=6, c=3, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 3.0, (c, f))
    ids = rng.integers(0, c, n)
    x = (centers[ids] + rng.normal(0, 0.5, (n, f))).astype(np.float32)
    return DataSet(x, np.eye(c, dtype=np.float32)[ids])


def _conf(f=6, c=3, lr=0.05):
    return NeuralNetConfiguration(
        seed=7, updater=updaters.Adam(learning_rate=lr)
    ).list([Dense(n_out=16, activation="relu"),
            Output(n_out=c, loss="mcxent")]).set_input_type(it.feed_forward(f))


def test_export_and_file_shard_roundtrip(tmp_path):
    ds = _ds()
    paths = export_dataset_batches(ListDataSetIterator(ds, batch=30),
                                   str(tmp_path), "train")
    assert len(paths) == 4
    back = FileShardDataSetIterator(str(tmp_path))
    feats = np.concatenate([d.features for d in back])
    np.testing.assert_allclose(feats, ds.features, atol=0)
    # sharded read: 2 shards partition the files
    s0 = FileShardDataSetIterator(str(tmp_path), 0, 2)
    s1 = FileShardDataSetIterator(str(tmp_path), 1, 2)
    assert len(s0.paths) == len(s1.paths) == 2
    assert set(s0.paths).isdisjoint(s1.paths)


def test_batch_and_export_rebatches(tmp_path):
    ds = _ds(n=100)
    paths = batch_and_export(ListDataSetIterator(ds, batch=30),
                             str(tmp_path), batch_size=40)
    sizes = [FileShardDataSetIterator(p).batch_size() for p in
             sorted(paths)]
    assert sizes == [40, 40, 20]  # tail preserved


def test_rebatching_iterator_even_and_tail():
    ds = _ds(n=70)
    rb = RebatchingDataSetIterator(ListDataSetIterator(ds, batch=7), 32)
    sizes = [d.features.shape[0] for d in rb]
    assert sizes == [32, 32, 6]
    # content preserved in order
    rb.reset()
    feats = np.concatenate([d.features for d in rb])
    np.testing.assert_allclose(feats, ds.features, atol=0)
    # drop_last drops the tail
    rb2 = RebatchingDataSetIterator(ListDataSetIterator(ds, batch=7), 32,
                                    drop_last=True)
    assert [d.features.shape[0] for d in rb2] == [32, 32]


def test_split_for_workers():
    parts = split_for_workers(ListDataSetIterator(_ds(n=120), batch=20), 3)
    assert len(parts) == 3
    assert all(sum(d.features.shape[0] for d in p) == 40 for p in parts)


def test_distributed_early_stopping():
    from deeplearning4j_tpu.earlystopping.core import (
        DataSetLossCalculator,
        EarlyStoppingConfiguration,
        InMemoryModelSaver,
        MaxEpochsTerminationCondition,
    )

    ds = _ds()
    net = MultiLayerNetwork(_conf()).init()
    cfg = EarlyStoppingConfiguration(
        epoch_termination_conditions=[MaxEpochsTerminationCondition(4)],
        score_calculator=DataSetLossCalculator(
            ListDataSetIterator(ds, batch=40)),
        model_saver=InMemoryModelSaver(),
    )
    master = ParameterAveragingTrainingMaster(num_workers=2,
                                              batches_per_worker=2)
    trainer = DistributedEarlyStoppingTrainer(
        cfg, master, net, ListDataSetIterator(ds, batch=20,
                                              shuffle_each_epoch=True))
    result = trainer.fit()
    assert result.total_epochs <= 5
    scores = list(result.score_vs_epoch.values())
    assert scores[-1] < scores[0]
    assert result.get_best_model() is not None


def test_text_pipeline_merged_vocab():
    corpus = ["the cat sat", "the dog sat", "a cat ran"] * 3
    seqs, vocab = TextPipeline(min_word_frequency=2, num_partitions=3).run(
        corpus)
    assert len(seqs) == 9
    assert "cat" in vocab and "the" in vocab
    w = vocab.word_for("the")
    assert w.count == 6  # counts merged across partitions


def test_distributed_word2vec_trains_and_merges():
    corpus = (["king queen royal palace"] * 20
              + ["dog cat pet animal"] * 20
              + ["king palace dog"] * 2)
    dw = DistributedWord2Vec(num_workers=2, layer_size=24, epochs=3,
                             min_word_frequency=1, seed=5)
    dw.fit(corpus)
    assert dw.word_vector("king") is not None
    assert dw.similarity("king", "queen") > dw.similarity("king", "cat")


def test_streaming_pipeline_end_to_end():
    net = MultiLayerNetwork(_conf()).init()
    ds = _ds(n=8)
    t_in, t_out = Topic("in"), Topic("out")
    results = t_out.subscribe()
    pipe = StreamingInferencePipeline(net, t_in, t_out, workers=2).start()
    for row in ds.features:
        t_in.publish(row)
    got = [next(results) for _ in range(8)]
    pipe.stop()
    assert all(g.shape == (3,) for g in got)
    assert all(abs(g.sum() - 1.0) < 1e-4 for g in got)


def test_streaming_multi_worker_no_duplicates():
    """Workers are competing consumers: each record inferred exactly once."""
    t_in, t_out = Topic("in"), Topic("out")
    results = t_out.subscribe()
    pipe = StreamingInferencePipeline(lambda x: x * 2.0, t_in, t_out,
                                      workers=3).start()
    for i in range(9):
        t_in.publish(np.full((2,), float(i), np.float32))
    got = sorted(float(next(results)[0]) for _ in range(9))
    pipe.stop()
    assert got == [float(2 * i) for i in range(9)]  # no dupes, none lost


def test_roc_thresholded_curve_area_positive():
    """Thresholded mode emits descending-x curves; area() must sort."""
    from deeplearning4j_tpu.eval.roc import ROC

    rng = np.random.default_rng(3)
    labels = rng.integers(0, 2, 300)
    scores = np.clip(labels * 0.5 + rng.normal(0.25, 0.2, 300), 0, 1)
    roc = ROC(threshold_steps=30)
    roc.eval(labels.astype(np.float32), scores.astype(np.float32))
    assert roc.roc_curve().area() > 0.5
    assert roc.precision_recall_curve().area() > 0.5
    assert abs(roc.roc_curve().area() - roc.calculate_auc()) < 0.05


def test_network_estimator_sklearn_protocol():
    ds = _ds(n=150)
    y_int = ds.labels.argmax(axis=-1)
    est = NetworkEstimator(conf=_conf(), epochs=30, batch_size=32)
    est.fit(ds.features, y_int)
    assert est.score(ds.features, y_int) > 0.8
    proba = est.predict_proba(ds.features)
    assert proba.shape == (150, 3)
    np.testing.assert_allclose(proba.sum(axis=1), 1.0, atol=1e-4)
    # param protocol
    est.set_params(epochs=1)
    assert est.get_params()["epochs"] == 1
    with pytest.raises(ValueError):
        est.set_params(bogus=1)
    # works inside an sklearn-style pipeline composition (duck-typed)
    assert est.transform(ds.features[:4]).shape == (4, 3)


def test_network_estimator_with_master():
    ds = _ds(n=120)
    est = NetworkEstimator(
        conf=_conf(), epochs=10, batch_size=20,
        master=ParameterAveragingTrainingMaster(num_workers=2))
    est.fit(ds, None)
    assert est.score(ds.features, ds.labels) > 0.6


def test_streaming_pipeline_across_process_boundary(tmp_path):
    """The serving pipeline over a REAL process boundary (the
    EmbeddedKafkaCluster test role): a child process restores the model,
    serves it over TCP with length-prefixed npy frames, and the parent's
    predictions must match local inference bit-for-bit — proving wire
    serialization round-trips."""
    import os
    import subprocess
    import sys

    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models import MultiLayerNetwork, write_model
    from deeplearning4j_tpu.nn import inputs as it
    from deeplearning4j_tpu.nn import updaters
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import Dense, Output
    from deeplearning4j_tpu.distributed.streaming import (
        StreamingInferenceClient,
    )

    conf = NeuralNetConfiguration(
        seed=5, updater=updaters.Adam(1e-3),
    ).list([
        Dense(n_out=12, activation="tanh"),
        Output(n_out=3, loss="mcxent"),
    ]).set_input_type(it.feed_forward(6))
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((16, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
    net.fit(DataSet(x, y))
    zip_path = str(tmp_path / "model.zip")
    write_model(net, zip_path)

    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # a child never shares the parent's chip
    proc = subprocess.Popen(
        [sys.executable, os.path.join(here, "stream_server_worker.py"),
         zip_path],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        line = proc.stdout.readline()
        assert line.startswith("PORT "), (line, proc.stderr.read())
        port = int(line.split()[1])

        client = StreamingInferenceClient("127.0.0.1", port)
        records = [rng.standard_normal(6).astype(np.float32)
                   for _ in range(5)]
        preds = [client.predict(r) for r in records]
        local = np.asarray(net.output(np.stack(records)))
        np.testing.assert_allclose(np.stack(preds), local, atol=1e-6)

        # streaming batch mode: pipeline + end-of-stream drain
        for r in records:
            client.send(r)
        rest = client.finish()
        assert len(rest) == len(records)
        np.testing.assert_allclose(np.stack(rest), local, atol=1e-6)
        client.close()
    finally:
        proc.kill()
        proc.wait()
