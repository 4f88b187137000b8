"""Integer class labels (ROADMAP R1): `DataSet(x, int32 [b] or [b, t])`
gives the loss, the gradients and the evaluation its one-hot array gives, on
MultiLayerNetwork, ComputationGraph and ParallelWrapper; dense labels keep
their path."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.eval import Evaluation
from deeplearning4j_tpu.models import ComputationGraph, MultiLayerNetwork
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn import losses, updaters
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import (
    Dense,
    EmbeddingSequence,
    LossLayer,
    Output,
    RnnOutput,
)
from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper

C = 7


def flat_net(seed=3, loss="mcxent", activation="softmax"):
    return MultiLayerNetwork(NeuralNetConfiguration(
        seed=seed, updater=updaters.Sgd(0.5)).list([
            Dense(n_out=12, activation="tanh"),
            Output(n_out=C, loss=loss, activation=activation),
        ]).set_input_type(it.feed_forward(5))).init()


def seq_net(seed=3, head=None):
    return MultiLayerNetwork(NeuralNetConfiguration(
        seed=seed, updater=updaters.Sgd(0.5)).list([
            EmbeddingSequence(n_in=C, n_out=8),
            head or RnnOutput(n_out=C, loss="mcxent", activation="softmax"),
        ]).set_input_type(it.recurrent(C, 6))).init()


def graph_net(seed=3):
    conf = (NeuralNetConfiguration(seed=seed, updater=updaters.Sgd(0.5)).graph()
            .add_inputs("in")
            .add_layer("h", Dense(n_out=12, activation="tanh"), "in")
            .add_layer("out", Output(n_out=C, loss="mcxent"), "h")
            .set_outputs("out").set_input_types(it.feed_forward(5)).build())
    return ComputationGraph(conf).init()


def flat_data(rng, n=16):
    x = rng.standard_normal((n, 5)).astype(np.float32)
    ids = rng.integers(0, C, n).astype(np.int32)
    return x, ids, np.eye(C, dtype=np.float32)[ids]


def seq_data(rng, n=16, t=6):
    x = rng.integers(0, C, (n, t)).astype(np.int32)
    ids = np.roll(x, -1, 1).astype(np.int32)
    return x, ids, np.eye(C, dtype=np.float32)[ids]


def leaves(net):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(jax.device_get(net.params))]


CASES = {
    "mln_output": (flat_net, flat_data),
    "mln_rnn_output": (seq_net, seq_data),
    "mln_loss_layer": (lambda: MultiLayerNetwork(
        NeuralNetConfiguration(seed=3, updater=updaters.Sgd(0.5)).list([
            EmbeddingSequence(n_in=C, n_out=C),
            LossLayer(loss="mcxent", activation="softmax"),
        ]).set_input_type(it.recurrent(C, 6))).init(), seq_data),
    "mln_mse_expands_the_index": (lambda: flat_net(loss="mse", activation="identity"),
                                  flat_data),
    "graph_output": (graph_net, flat_data),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_integer_and_one_hot_labels_give_the_same_loss_and_step(case, rng):
    build, data = CASES[case]
    x, ids, onehot = data(rng)
    a, b = build(), build()
    np.testing.assert_allclose(a.score(DataSet(x, ids)), b.score(DataSet(x, onehot)),
                               rtol=1e-6)
    a.fit(DataSet(x, ids))
    b.fit(DataSet(x, onehot))
    np.testing.assert_allclose(a.score_, b.score_, rtol=1e-6)
    for p, q in zip(leaves(a), leaves(b)):      # one SGD step: the gradients
        np.testing.assert_allclose(p, q, atol=1e-6)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs the 8-device CPU mesh")
@pytest.mark.parametrize("masked", [False, True], ids=["", "masked"])
@pytest.mark.parametrize("build,data", [(flat_net, flat_data), (seq_net, seq_data)],
                         ids=["output", "rnn_output"])
def test_parallel_wrapper_takes_integer_labels(build, data, masked, rng):
    """Under the data mesh each device scores its own rows
    (`per_batch_shard`: 16 rows a batch over 8 devices) with its rows' share
    of the weights — under a mask their denominator is the whole batch's —
    and the shares add up to the one-device score and step."""
    x, ids, onehot = data(rng, n=32)
    mask = {}
    if masked:
        mask = {"labels_mask": (rng.uniform(size=ids.shape) > 0.4).astype(np.float32)}
    a, b, c = build(), build(), build()
    ParallelWrapper(a, mesh_spec=MeshSpec(data=8)).fit(
        ListDataSetIterator(DataSet(x, ids, **mask), batch=16), epochs=2)
    ParallelWrapper(b, mesh_spec=MeshSpec(data=8)).fit(
        ListDataSetIterator(DataSet(x, onehot, **mask), batch=16), epochs=2)
    c.fit(ListDataSetIterator(DataSet(x, ids, **mask), batch=16), epochs=2)
    for p, q, r in zip(leaves(a), leaves(b), leaves(c)):
        np.testing.assert_allclose(p, q, atol=2e-6)
        np.testing.assert_allclose(p, r, atol=2e-5)


def test_masked_integer_labels(rng):
    x, ids, onehot = seq_data(rng)
    mask = (rng.uniform(size=ids.shape) > 0.3).astype(np.float32)
    net = seq_net()
    np.testing.assert_allclose(
        net.score(DataSet(x, ids, labels_mask=mask)),
        net.score(DataSet(x, onehot, labels_mask=mask)), rtol=1e-6)


def test_head_and_loss_in_row_blocks_equal_the_whole(rng):
    n, f, c = 4096, 16, 33                   # two blocks of 2048 rows
    x = jnp.asarray(rng.standard_normal((n, f)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((f, c)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((c,)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, c, n), jnp.int32)

    def whole(x_, w_, b_):
        logp = jax.nn.log_softmax(x_ @ w_ + b_)
        return -jnp.take_along_axis(logp, ids[:, None], 1)[:, 0]

    np.testing.assert_allclose(losses.sparse_xent_rows(x, w, b, ids), whole(x, w, b),
                               atol=1e-5)
    text = str(jax.make_jaxpr(lambda *a: losses.sparse_xent_rows(*a, ids))(x, w, b))
    assert f"f32[{n},{c}]" not in text and f"f32[2048,{c}]" in text
    g1 = jax.grad(lambda *a: losses.sparse_xent_rows(*a, ids).sum(), (0, 1, 2))(x, w, b)
    g2 = jax.grad(lambda *a: whole(*a).sum(), (0, 1, 2))(x, w, b)
    for p, q in zip(g1, g2):
        np.testing.assert_allclose(p, q, atol=1e-4 * float(jnp.abs(q).max()))
    assert not losses.is_class_index(jnp.zeros((4, c)), 2)      # dense labels
    assert not losses.is_class_index(jnp.zeros((4, c), jnp.int32), 2)
    assert losses.is_class_index(jnp.zeros((4,), jnp.int32), 2)


def test_evaluation_takes_integer_labels(rng):
    x, ids, onehot = seq_data(rng)
    net = seq_net()
    out = net.output(x)
    a, b = Evaluation(), Evaluation()
    a.eval(ids, out)
    b.eval(onehot, out)
    np.testing.assert_array_equal(a.confusion.matrix, b.confusion.matrix)
    ev = net.evaluate(ListDataSetIterator(DataSet(x, ids), batch=8))
    assert ev.accuracy() == a.accuracy()


@pytest.mark.parametrize("masked", [False, True])
def test_evaluation_of_integer_labels_builds_no_class_axis(rng, masked):
    """At a vocabulary in the tens of thousands the integers are the actual
    classes: no [V, V] identity (1.6 GB here) and no [b, t, V] one-hot."""
    import tracemalloc

    v, b, t = 20_000, 4, 16
    ids = rng.integers(0, v, (b, t)).astype(np.int32)
    out = rng.standard_normal((b, t, v)).astype(np.float32)
    hit = rng.random((b, t)) < 0.5
    np.put_along_axis(out, ids[..., None], np.where(hit, 9.0, -9.0)[..., None], axis=-1)
    mask = (rng.random((b, t)) < 0.7).astype(np.float32) if masked else None
    ev = Evaluation()
    ev._ensure(v)                 # the [V, V] counts are Evaluation's own
    tracemalloc.start()
    ev.eval(ids, out, mask)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak < 4 * out.nbytes
    keep = np.ones((b, t), bool) if mask is None else mask > 0
    assert ev.total == int(keep.sum())
    assert ev.top_n_correct == int((hit & keep).sum())
    first = np.argwhere(hit & keep)[0]
    c = int(ids[tuple(first)])
    assert ev.confusion.get_count(c, c) >= 1


def whole_weighted(x, w, b, ids, row_weights):
    """The weighted sum of the rows' cross-entropy from the whole [n, c]
    logits, float32."""
    logp = jax.nn.log_softmax(x.astype(jnp.float32) @ w.astype(jnp.float32) + b)
    return jnp.sum(row_weights * -jnp.take_along_axis(logp, ids[:, None], 1)[:, 0])


def checkpoint_weighted(x, w, b, ids, row_weights):
    """The form before `sparse_xent_weighted`: the per-row values, their
    blocks under `jax.checkpoint`, weighted afterwards."""
    return jnp.sum(row_weights * losses.sparse_xent_rows(x, w, b, ids))


def weighted_case(rng, n, dtype, f=16, c=33):
    x = jnp.asarray(rng.standard_normal((n, f)), dtype)
    w = jnp.asarray(rng.standard_normal((f, c)), dtype)
    b = jnp.asarray(rng.standard_normal((c,)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, c, n), jnp.int32)
    # random weights, three in ten of them zero: a mask
    row_weights = jnp.asarray(rng.uniform(size=n) * (rng.uniform(size=n) > 0.3), jnp.float32)
    return x, w, b, ids, row_weights


@pytest.mark.parametrize("n", [4096, 1000], ids=["two_blocks", "one_block_no_block_divides"])
def test_weighted_head_and_loss_against_the_whole_array(n, rng):
    x, w, b, ids, row_weights = weighted_case(rng, n, jnp.float32)
    got, ce = losses.sparse_xent_weighted(x, w, b, ids, row_weights)       # no gradient asked
    np.testing.assert_allclose(got, whole_weighted(x, w, b, ids, row_weights), rtol=1e-5)
    np.testing.assert_allclose(ce, losses.sparse_xent_rows(x, w, b, ids), atol=1e-6)
    assert ce.dtype == jnp.float32
    value, grads = jax.value_and_grad(
        lambda x_, w_, b_, r_: losses.sparse_xent_weighted(x_, w_, b_, ids, r_)[0],
        (0, 1, 2, 3))(x, w, b, row_weights)
    np.testing.assert_allclose(value, got, rtol=1e-6)
    want = jax.grad(lambda *a: whole_weighted(a[0], a[1], a[2], ids, a[3]),
                    (0, 1, 2, 3))(x, w, b, row_weights)
    for p, q in zip(grads, want):
        np.testing.assert_allclose(p, q, atol=1e-5 * float(jnp.abs(q).max()))
    np.testing.assert_array_equal(grads[3], ce)          # d/d(row_weights) IS the row's ce
    assert float(jnp.abs(grads[0][row_weights == 0]).max()) == 0.0      # a masked row gets none
    # a head without a bias, and a cotangent that is not 1 (the pipeline step's `wt`)
    g3 = jax.grad(lambda x_, w_: 0.25 * losses.sparse_xent_weighted(
        x_, w_, None, ids, row_weights)[0], (0, 1))(x, w)
    w3 = jax.grad(lambda x_, w_: 0.25 * whole_weighted(x_, w_, 0.0, ids, row_weights),
                  (0, 1))(x, w)
    for p, q in zip(g3, w3):
        np.testing.assert_allclose(p, q, atol=1e-5 * float(jnp.abs(q).max()))
    # no gradient flows through the per-row values
    assert all(float(jnp.abs(g).max()) == 0.0 for g in jax.grad(
        lambda x_, w_: losses.sparse_xent_weighted(x_, w_, b, ids, row_weights)[1].sum(),
        (0, 1))(x, w))


@pytest.mark.parametrize("n", [4096, 1000], ids=["two_blocks", "one_block_no_block_divides"])
def test_weighted_head_and_loss_in_bf16_against_the_checkpoint_form(n, rng):
    """bf16 x and w: the logits are bf16 and the float32 logsumexp reads
    them, in both forms, so the values are equal to the last bit. The
    gradients are not: dz is rounded to bf16 in both, but from
    weight (e^(z - lse) - hit) here and from autodiff's
    weight e^(z - max) / sum - weight hit there, so a dz may differ by ONE
    bf16 ulp (2^-8 relative), and dx, dw, db — sums of c or of n of them,
    db's in bf16 — by a few ulps of their largest element. Neither form is
    further from the float32 gradient of the same bf16 inputs than that."""
    x, w, b, ids, row_weights = weighted_case(rng, n, jnp.bfloat16)
    np.testing.assert_array_equal(
        losses.sparse_xent_weighted(x, w, b, ids, row_weights)[0],
        checkpoint_weighted(x, w, b, ids, row_weights))
    got = jax.grad(lambda *a: losses.sparse_xent_weighted(a[0], a[1], a[2], ids, a[3])[0],
                   (0, 1, 2, 3))(x, w, b, row_weights)
    want = jax.grad(lambda *a: checkpoint_weighted(a[0], a[1], a[2], ids, a[3]),
                    (0, 1, 2, 3))(x, w, b, row_weights)
    exact = jax.grad(lambda *a: whole_weighted(a[0], a[1], a[2], ids, a[3]), (0, 1, 2, 3))(
        x.astype(jnp.float32), w.astype(jnp.float32), b, row_weights)
    for p, q, r in zip(got, want, exact):
        assert p.dtype == q.dtype
        few_ulps = 2.0 ** -6 * float(jnp.abs(r).max())
        np.testing.assert_allclose(np.asarray(p, np.float32), np.asarray(q, np.float32),
                                   atol=few_ulps)
        np.testing.assert_allclose(np.asarray(p, np.float32), r, atol=few_ulps)


def test_the_weighted_gradient_multiplies_the_head_three_times(rng):
    n, f, c = 4096, 16, 33
    x, w, b, ids, row_weights = weighted_case(rng, n, jnp.float32, f, c)

    def score(x_, w_, b_, r_):
        return losses.sparse_xent_weighted(x_, w_, b_, ids, r_)[0]

    def products(jaxpr_text):            # the shape of every product's result
        return sorted(re.findall(r":f32\[(\d+,\d+)\] = dot_general", jaxpr_text))

    grad = str(jax.make_jaxpr(jax.grad(score, (0, 1, 2, 3)))(x, w, b, row_weights))
    assert f"f32[{n},{c}]" not in grad and f"f32[2048,{c}]" in grad
    # a block's logits, its dx and its dw: three of head size, and no fourth
    assert products(grad) == sorted([f"2048,{c}", f"2048,{f}", f"{f},{c}"])
    assert grad.count("dot_general") == 3
    assert "checkpoint" not in grad and "remat" not in grad
    primal = str(jax.make_jaxpr(score)(x, w, b, row_weights))
    assert f"f32[{n},{c}]" not in primal and products(primal) == [f"2048,{c}"]
    # the form it replaces under a gradient: the logits a second time
    old = str(jax.make_jaxpr(jax.grad(checkpoint_weighted, (0, 1, 2)))(
        x, w, b, ids, row_weights))
    assert len(products(old)) == 4 and products(old).count(f"2048,{c}") == 2
