"""`LoopedStack` (nn/layers/blocks.py): a list of nested blocks applied
`steps` times over ONE set of parameters. `steps` passes equal `steps`
explicit applications of one parameter tree; the tree is one pass's; a tied
leaf's gradient is the SUM of the gradients of `steps` untied copies holding
the same values; `steps=1` is the plain list bit for bit; remat on and off
agree; the JSON and a model zip round-trip a looped net; a nested layer that
keeps state, or that changes its input's type, is refused by name."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import zoo
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models import MultiLayerNetwork, serialization
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn import updaters
from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import (
    Dense,
    EmbeddingSequence,
    GatedAttention,
    GatedMLP,
    LoopedStack,
    LoopExitOutput,
    RMSNorm,
    RnnOutput,
    RoutedExperts,
    SubLayerBlock,
)
from deeplearning4j_tpu.nn.layers.base import Layer

T, F, VOCAB, STEPS = 24, 16, 11, 4
IN = it.recurrent(F, T)
RUN = dict(state={}, train=True, rng=None)


def blocks(remat=None, post_norm=True):
    return [
        SubLayerBlock(sub=GatedAttention(n_heads=2, n_kv_heads=2, head_dim=8, rotary_fraction=1.0,
                                         rope_theta=1e4, gated=False, qk_norm=False),
                      eps=1e-6, post_norm=post_norm, remat=remat),
        SubLayerBlock(sub=GatedMLP(width=24, act="swiglu"), eps=1e-6, post_norm=post_norm,
                      remat=remat),
        RMSNorm(eps=1e-6, zero_centered=False),
    ]


def seeded(stack, seed=0):
    """Parameters that are no layer's starting values (norm weights off 1)."""
    params = stack.init_params(jax.random.PRNGKey(seed), IN)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        a + 0.1 * jax.random.normal(k, a.shape, a.dtype) for a, k in zip(leaves, keys)])


def explicit(layers, trees, x, mask=None):
    """The list applied once a tree of `trees`, by hand: the passes' outputs."""
    outs = []
    for params in trees:
        for j, layer in enumerate(layers):
            x, _ = layer.apply(params[str(j)], x, mask=mask, **RUN)
        outs.append(x)
    return jnp.stack(outs, axis=1)


@pytest.fixture
def x(rng):
    return jnp.asarray(rng.normal(size=(2, T, F)), jnp.float32)


@pytest.mark.parametrize("masked", [False, True])
def test_steps_passes_are_steps_applications_of_one_tree(masked, x, rng):
    stack = LoopedStack(layers=blocks(), steps=STEPS)
    params = seeded(stack)
    mask = jnp.asarray(rng.integers(0, 2, (2, T)), jnp.float32).at[:, 0].set(1.0) if masked else None
    got, state = stack.apply(params, x, mask=mask, **RUN)
    assert got.shape == (2, STEPS, T, F) and state == {}
    assert stack.output_type(IN) == it.RecurrentPasses(F, T, passes=STEPS)
    assert stack.output_type(IN).shape(2) == got.shape
    np.testing.assert_allclose(got, explicit(stack.layers, [params] * STEPS, x, mask),
                               rtol=2e-4, atol=2e-5)
    # pass s + 1 read what pass s wrote: the passes differ
    assert float(jnp.abs(got[:, 1] - got[:, 0]).max()) > 1e-3


def test_the_parameter_tree_is_one_passes():
    stack = LoopedStack(layers=blocks(), steps=STEPS)
    params = stack.init_params(jax.random.PRNGKey(0), IN)
    assert sorted(params) == ["0", "1", "2"]
    once = [layer.init_params(jax.random.PRNGKey(0), IN) for layer in stack.layers]
    assert (len(jax.tree.leaves(params)) == sum(len(jax.tree.leaves(p)) for p in once)
            == 4 + 4 + 1)
    net = MultiLayerNetwork(looped_conf()).init()
    per_pass = sum(int(a.size) for p in once for a in jax.tree.leaves(p))
    assert net.num_params() == VOCAB * F + per_pass + F * VOCAB + F + 1
    assert sorted(net.params["layer_1"]) == ["0", "1", "2"]


def test_a_tied_leafs_gradient_is_the_sum_over_untied_copies(x):
    stack = LoopedStack(layers=blocks(), steps=STEPS)
    params = seeded(stack)
    weigh = jnp.asarray(np.random.default_rng(3).normal(size=(2, STEPS, T, F)), jnp.float32)

    tied = jax.grad(lambda p: jnp.sum(stack.apply(p, x, **RUN)[0] * weigh))(params)
    untied = jax.grad(lambda trees: jnp.sum(explicit(stack.layers, trees, x) * weigh))(
        [params] * STEPS)
    summed = jax.tree.map(lambda *g: sum(g), *untied)
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(tied),
                                 jax.tree.leaves(summed)):
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5 * scale, err_msg=str(path))
    # and no single pass's gradient is the whole of it
    one = jax.tree.leaves(untied[-1])[0]
    assert float(jnp.abs(jax.tree.leaves(tied)[0] - one).max()) > 1e-4


def test_one_step_is_the_plain_list_bit_for_bit(x):
    stack = LoopedStack(layers=blocks(), steps=1)
    params = seeded(stack)
    got, _ = jax.jit(lambda p, a: stack.apply(p, a, **RUN))(params, x)
    want = jax.jit(lambda p, a: explicit(stack.layers, [p], a))(params, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def grads(f):
        return jax.jit(jax.grad(lambda p, a: jnp.sum(jnp.sin(f(p, a)))))(params, x)

    for a, b in zip(jax.tree.leaves(grads(lambda p, a: stack.apply(p, a, **RUN)[0])),
                    jax.tree.leaves(grads(lambda p, a: explicit(stack.layers, [p], a)))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_remat_on_and_off_agree(x):
    outs = []
    for remat in (None, "full"):
        stack = LoopedStack(layers=blocks(remat=remat), steps=STEPS)
        params = seeded(stack)
        value, grad = jax.value_and_grad(
            lambda p: jnp.sum(jnp.sin(stack.apply(p, x, **RUN)[0])))(params)
        outs.append((value, grad))
    np.testing.assert_allclose(outs[0][0], outs[1][0], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(outs[0][1]), jax.tree.leaves(outs[1][1])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6 * float(jnp.abs(b).max()))
    # each nested block's remat wraps each of its applications: a checkpoint a
    # block and pass, none around the stack, none outside training
    stack = LoopedStack(layers=blocks(remat="full"), steps=STEPS)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: jnp.sum(stack.apply(p, x, **RUN)[0])))(seeded(stack)))
    assert text.count("remat2") == 2 * STEPS
    assert "remat2" not in str(jax.make_jaxpr(
        lambda p: stack.apply(p, x, state={}, train=False, rng=None)[0])(seeded(stack)))


def test_a_fresh_rng_a_pass_and_a_nested_layer(x):
    """With an rng every application of every nested layer gets a key of its
    own; without, none."""
    seen = []

    class Probe(RMSNorm):
        def apply(self, params, a, *, state, train, rng, mask=None):
            seen.append(rng)
            return super().apply(params, a, state=state, train=train, rng=rng, mask=mask)

    stack = LoopedStack(layers=[Probe(eps=1e-6), Probe(eps=1e-6)], steps=3)
    params = stack.init_params(jax.random.PRNGKey(0), IN)
    stack.apply(params, x, state={}, train=True, rng=None)
    assert seen == [None] * 6
    del seen[:]
    stack.apply(params, x, state={}, train=True, rng=jax.random.PRNGKey(4))
    keys = {tuple(np.asarray(jax.random.key_data(k)).ravel().tolist()) for k in seen}
    assert len(seen) == 6 and len(keys) == 6


def looped_conf(steps=STEPS, remat=None):
    return NeuralNetConfiguration(seed=5, updater=updaters.Adam(learning_rate=1e-2)).list([
        EmbeddingSequence(n_in=VOCAB, n_out=F),
        LoopedStack(layers=blocks(remat=remat), steps=steps),
        LoopExitOutput(n_out=VOCAB, loss="mcxent", activation="softmax", has_bias=False),
    ]).set_input_type(it.recurrent(VOCAB, T))


def test_json_and_a_model_zip_round_trip_a_looped_net(tmp_path, rng):
    conf = looped_conf(remat="full")
    text = conf.to_json()
    written = json.loads(text)["layers"][1]
    assert written["type"] == "LoopedStack" and written["steps"] == STEPS
    assert [l["type"] for l in written["layers"]] == ["SubLayerBlock", "SubLayerBlock", "RMSNorm"]
    assert written["layers"][0]["sub"]["type"] == "GatedAttention"
    assert written["layers"][0]["post_norm"] is True
    again = MultiLayerConfiguration.from_json(text)
    assert again.to_json() == text
    assert again.layers[1] == conf.layers[1]
    assert all(isinstance(l, Layer) for l in again.layers[1].layers)
    net = MultiLayerNetwork(conf).init()
    ids = rng.integers(0, VOCAB, (2, T)).astype(np.int32)
    ds = DataSet(ids, np.roll(ids, -1, 1).astype(np.int32))
    first = net.score(ds)
    net.fit(ds)
    net.fit(ds)
    assert np.isfinite(net.score(ds)) and net.score(ds) < first
    path = str(tmp_path / "looped.zip")
    serialization.write_model(net, path)
    back = serialization.restore_multi_layer_network(path)
    assert net.output(ids).shape == (2, T, VOCAB)
    np.testing.assert_array_equal(back.output(ids), net.output(ids))
    assert back.score(ds) == net.score(ds)
    assert jax.tree.structure(back.params) == jax.tree.structure(net.params)


REFUSED = {
    "a layer that keeps state": (
        lambda: [SubLayerBlock(sub=RoutedExperts(n_experts=4, top_k=2, expert_width=8))],
        r"layers\[0\] \(SubLayerBlock\) keeps state"),
    "a layer that changes its input's type": (
        lambda: [RMSNorm(), Dense(n_out=F + 1)],
        r"layers\[1\] \(Dense\) does not keep its input's type"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_what_a_loop_cannot_thread_is_refused_by_name(case):
    layers, message = REFUSED[case]
    stack = LoopedStack(layers=layers(), steps=2)
    with pytest.raises(ValueError, match=message):
        stack.output_type(IN)
    with pytest.raises(ValueError, match=message):   # when the net is built, before any array
        MultiLayerNetwork(NeuralNetConfiguration(seed=1).list([
            EmbeddingSequence(n_in=VOCAB, n_out=F), stack,
            LoopExitOutput(n_out=VOCAB, loss="mcxent", activation="softmax"),
        ]).set_input_type(it.recurrent(VOCAB, T)))


@pytest.mark.parametrize("bad, error", [
    (dict(layers=[], steps=2), TypeError), (dict(layers=None, steps=2), TypeError),
    (dict(layers=[RMSNorm()], steps=0), ValueError), (dict(layers=[RMSNorm(), 3], steps=2), TypeError),
])
def test_a_stack_of_nothing_is_refused(bad, error):
    with pytest.raises(error):
        LoopedStack(**bad)


def test_only_the_exit_output_reads_the_passes():
    stack = LoopedStack(layers=[RMSNorm()], steps=2)
    with pytest.raises(ValueError, match="LoopedStack runs over"):
        stack.output_type(stack.output_type(IN))
    with pytest.raises(ValueError, match="LoopExitOutput reads a looped stack's passes"):
        LoopExitOutput(n_out=VOCAB).output_type(IN)
    # the plain head still takes [b, t, f] and nothing else changed for it
    assert RnnOutput(n_out=VOCAB).output_type(IN) == it.recurrent(VOCAB, T)
    assert it.from_json(stack.output_type(IN).to_json()) == stack.output_type(IN)


def test_zoo_loop_lm_is_the_stack_between_embedding_and_exit_output():
    m = zoo.LoopLM(vocab_size=VOCAB, hidden_size=F, max_length=T, num_hidden_layers=3,
                   num_attention_heads=2, num_key_value_heads=2, head_dim=8,
                   intermediate_size=24, total_ut_steps=3, beta=0.2, remat="full")
    layers = m.conf().layers
    assert [type(l).__name__ for l in layers] == ["EmbeddingSequence", "LoopedStack",
                                                  "LoopExitOutput"]
    stack, out = layers[1], layers[2]
    assert stack.steps == 3 and out.beta == 0.2 and not out.has_bias
    assert [type(l).__name__ for l in stack.layers] == ["SubLayerBlock"] * 6 + ["RMSNorm"]
    assert all(b.post_norm and b.remat == "full" and b.eps == 1e-6 for b in stack.layers[:-1])
    assert [type(b.sub).__name__ for b in stack.layers[:-1]] == ["GatedAttention", "GatedMLP"] * 3
    att = stack.layers[0].sub
    assert (att.rotary_fraction, att.gated, att.qk_norm, att.rope_theta) == (1.0, False, False, 1e6)
