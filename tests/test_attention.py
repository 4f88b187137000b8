"""Attention stack: SDPA/blockwise/ring numerics, transformer layers,
sequence-parallel training on the 8-device CPU mesh.

The reference has no attention (SURVEY.md §5) — these tests cover the
net-new long-context capability: exactness of the blockwise (flash) and ring
formulations vs full SDPA, layer integration with MultiLayerNetwork, and
gradient checks through a TransformerBlock.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn import updaters
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import (
    LayerNorm,
    MultiHeadAttention,
    PositionEmbedding,
    RnnOutput,
    TransformerBlock,
)
from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.ops import attention as att
from deeplearning4j_tpu.ops import ring


def _qkv(rng, b=2, h=4, t=32, d=16, dtype=np.float32):
    q = jnp.asarray(rng.standard_normal((b, h, t, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, h, t, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, h, t, d)), dtype)
    return q, k, v


class TestAttentionOps:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("use_mask", [False, True])
    def test_blockwise_matches_sdpa(self, rng, causal, use_mask):
        q, k, v = _qkv(rng)
        mask = (jnp.asarray(rng.random((2, 32)) > 0.2).astype(jnp.float32)
                if use_mask else None)
        ref = att.sdpa(q, k, v, mask=mask, causal=causal)
        blk = att.blockwise(q, k, v, mask=mask, causal=causal, block_size=8)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(blk),
                                   atol=2e-5, rtol=2e-5)

    def test_blockwise_ragged_tail(self, rng):
        q, k, v = _qkv(rng, t=37)  # 37 % 8 != 0 exercises the pad path
        ref = att.sdpa(q, k, v, causal=True)
        blk = att.blockwise(q, k, v, causal=True, block_size=8)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(blk),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("use_mask", [False, True])
    def test_ring_matches_sdpa(self, rng, causal, use_mask):
        q, k, v = _qkv(rng)
        mask = (jnp.asarray(rng.random((2, 32)) > 0.2).astype(jnp.float32)
                if use_mask else None)
        mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
        ref = att.sdpa(q, k, v, mask=mask, causal=causal)
        out = ring.ring_attention(q, k, v, mesh, mask=mask, causal=causal)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("use_mask", [False, True])
    def test_ring_hop_chunking_exact(self, rng, use_mask):
        """block_size sub-chunks each ring hop (per-chip memory drops
        from O(t_loc^2) to O(t_loc*block)) without changing values OR
        gradients — the round-3 long-context upgrade."""
        q, k, v = _qkv(rng, t=64)  # t_loc=16 per shard, chunked into 4
        mask = (jnp.asarray(rng.random((2, 64)) > 0.2).astype(jnp.float32)
                if use_mask else None)
        mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
        ref = ring.ring_attention(q, k, v, mesh, mask=mask, causal=True)
        out = ring.ring_attention(q, k, v, mesh, mask=mask, causal=True,
                                  block_size=4)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=2e-5, rtol=2e-5)
        sdpa_ref = att.sdpa(q, k, v, mask=mask, causal=True)
        np.testing.assert_allclose(np.asarray(sdpa_ref), np.asarray(out),
                                   atol=2e-5, rtol=2e-5)

        def loss_chunked(q, k, v):
            return ring.ring_attention(q, k, v, mesh, mask=mask,
                                       causal=True, block_size=4).sum()

        def loss_ref(q, k, v):
            return att.sdpa(q, k, v, mask=mask, causal=True).sum()

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        g_out = jax.grad(loss_chunked, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_out):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_ring_hop_chunking_ragged_tail(self, rng):
        """t_loc not divisible by block_size: the shared chunk loop PADS
        the tail (padded keys masked dead) instead of silently reverting
        to full-score materialization."""
        q, k, v = _qkv(rng, t=60)  # t_loc=15 per shard; block 4 -> pad 1
        mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
        ref = att.sdpa(q, k, v, causal=True)
        out = ring.ring_attention(q, k, v, mesh, causal=True,
                                  block_size=4)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=2e-5, rtol=2e-5)

    def test_ring_gradients_match(self, rng):
        """jax.grad flows through ppermute: ring grads == sdpa grads."""
        q, k, v = _qkv(rng, b=1, h=2, t=16, d=8)
        mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))

        def loss_ref(q, k, v):
            return att.sdpa(q, k, v, causal=True).sum()

        def loss_ring(q, k, v):
            return ring.ring_attention(q, k, v, mesh, causal=True).sum()

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_ring):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)


class TestAttentionLayers:
    def _net(self, causal=False, t=12, f=16):
        conf = NeuralNetConfiguration(
            seed=3, updater=updaters.Adam(learning_rate=1e-3),
        ).list([
            PositionEmbedding(max_len=64),
            TransformerBlock(n_heads=4, causal=causal),
            TransformerBlock(n_heads=4, causal=causal),
            RnnOutput(n_out=5, loss="mcxent", activation="softmax"),
        ]).set_input_type(it.recurrent(f, t))
        return MultiLayerNetwork(conf).init()

    def test_forward_shapes(self, rng):
        net = self._net()
        x = rng.standard_normal((4, 12, 16)).astype(np.float32)
        y = net.output(x)
        assert y.shape == (4, 12, 5)
        np.testing.assert_allclose(np.asarray(y).sum(-1), 1.0, atol=1e-5)

    def test_fit_reduces_loss(self, rng):
        net = self._net(causal=True)
        x = rng.standard_normal((16, 12, 16)).astype(np.float32)
        ids = rng.integers(0, 5, (16, 12))
        y = np.eye(5, dtype=np.float32)[ids]
        s0 = None
        for _ in range(30):
            net.fit(x, y)
            s0 = s0 if s0 is not None else net.score_
        assert net.score_ < s0

    def test_layer_norm(self, rng):
        ln = LayerNorm()
        x = jnp.asarray(rng.standard_normal((3, 7, 16)), jnp.float32)
        p = ln.init_params(jax.random.PRNGKey(0), it.recurrent(16))
        y, _ = ln.apply(p, x, state={}, train=False, rng=None)
        np.testing.assert_allclose(np.asarray(y.mean(-1)), 0.0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(y.std(-1)), 1.0, atol=1e-2)

    def test_mha_causality(self, rng):
        """Causal MHA output at position i must not depend on inputs > i."""
        mha = MultiHeadAttention(n_heads=2, causal=True)
        p = mha.init_params(jax.random.PRNGKey(1), it.recurrent(8, 6))
        x = jnp.asarray(rng.standard_normal((1, 6, 8)), jnp.float32)
        y0, _ = mha.apply(p, x, state={}, train=False, rng=None)
        x2 = x.at[0, 4:].set(99.0)  # perturb the future
        y1, _ = mha.apply(p, x2, state={}, train=False, rng=None)
        np.testing.assert_allclose(np.asarray(y0[0, :4]),
                                   np.asarray(y1[0, :4]), atol=1e-5)
        assert not np.allclose(np.asarray(y0[0, 5]), np.asarray(y1[0, 5]))

    def test_sincos_position_embedding(self, rng):
        pe = PositionEmbedding(mode="sincos", max_len=32)
        assert not pe.has_params()
        x = jnp.zeros((2, 10, 12), jnp.float32)
        y, _ = pe.apply({}, x, state={}, train=False, rng=None)
        assert y.shape == (2, 10, 12)
        assert not np.allclose(np.asarray(y[0, 0]), np.asarray(y[0, 5]))

    def test_serde_roundtrip(self):
        net = self._net()
        j = net.conf.to_json()
        from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
        conf2 = MultiLayerConfiguration.from_json(j)
        assert [type(l).__name__ for l in conf2.layers] == \
               [type(l).__name__ for l in net.conf.layers]


class TestSequenceParallel:
    def test_seq_sharded_forward_matches_local(self, rng):
        """Transformer forward under shard_map over the seq axis (ring
        attention + offset position embeddings) == unsharded forward."""
        f, t = 16, 32
        conf = NeuralNetConfiguration(seed=5).list([
            PositionEmbedding(max_len=64),
            TransformerBlock(n_heads=4, causal=True),
            RnnOutput(n_out=5, loss="mcxent", activation="softmax"),
        ]).set_input_type(it.recurrent(f, t))
        net = MultiLayerNetwork(conf).init()
        x = jnp.asarray(rng.standard_normal((2, t, f)), jnp.float32)

        ref = np.asarray(net.output(x))

        mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
        params, state = net.params, net.state

        def fwd(params, state, xl):
            with ring.sequence_parallel("seq"):
                acts, _, _, _ = net._forward(params, state, xl, train=False,
                                             rng=None)
            return acts

        sharded = jax.shard_map(
            fwd, mesh=mesh,
            in_specs=(P(), P(), P(None, "seq", None)),
            out_specs=P(None, "seq", None),
            check_vma=False,
        )
        out = np.asarray(sharded(params, state, x))
        np.testing.assert_allclose(ref, out, atol=2e-5, rtol=2e-5)


def test_flash_attention_d64_matches_sdpa(rng):
    """Head dim 64 (the TransformerLM bench shape) through the pallas
    kernel must match sdpa, and the TPU gate must admit exactly the
    measured shapes: d=64 and lane-aligned d."""
    from deeplearning4j_tpu.ops.pallas_kernels import flash_attention

    q, k, v = (jnp.asarray(rng.standard_normal((2, 3, 128, 64)) * 0.3,
                           jnp.float32) for _ in range(3))
    o = flash_attention(q, k, v, True, None, 128, 128, True)  # interpret
    ref = att.sdpa(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)

    # pin the real-TPU gate decision (backend monkeypatched to 'tpu')
    import unittest.mock as mock

    from deeplearning4j_tpu.ops import pallas_kernels as pk

    def flash(impl, b, t, d, masked=False):
        return att.choose_impl(impl, b, t, d, masked) == "flash"

    with mock.patch("jax.default_backend", return_value="tpu"), \
            mock.patch.object(pk, "helpers_enabled", return_value=True):
        # admission is the shape rule alone (no compile probe): auto
        # admits t >= 512; below that XLA's materialized-scores path
        # holds
        assert flash("auto", 4, 1024, 64)       # long-context path
        assert flash("auto", 4, 2048, 128)      # lane-aligned
        assert flash("auto", 4, 512, 64)        # bench shape
        assert not flash("auto", 4, 256, 64)    # short: sdpa
        assert not flash("auto", 4, 1024, 96)   # untileable dim
        assert not flash("auto", 4, 1000, 64)   # non-block t
        assert not flash("auto", 4, 1024, 64, masked=True)  # masked input
        # explicit request skips the length gate
        assert flash("pallas", 4, 256, 64)
        # under a data mesh the kernel runs per batch shard: the batch
        # must split evenly; a mesh sharding anything else declines auto
        # and refuses a forced kernel call
        from deeplearning4j_tpu.parallel import MeshSpec, build_mesh

        with jax.set_mesh(build_mesh(MeshSpec(data=8))):
            assert flash("auto", 8, 1024, 64)
            assert not flash("auto", 6, 1024, 64)
        from deeplearning4j_tpu.ops import kernel_call

        with jax.set_mesh(build_mesh(MeshSpec(data=4, model=2))):
            assert not flash("auto", 8, 1024, 64)
            with pytest.raises(ValueError, match="per 'data' shard"):
                kernel_call.per_batch_shard(lambda a: a, (q,), (True,))


@pytest.mark.parametrize("shape, blocks", [
    ((8, 12, 1024, 64), (256, 256)),    # gpt2s_train_t1024, _ids
    ((2, 16, 8192, 256), (512, 512)),   # qwen3next_train_t8192
])
def test_benchmark_cells_choose_flash(shape, blocks):
    """The rule at the shapes the benchmark's cells hand it, on a TPU
    backend: flash, with the block plan their kernel names carry (GPT-2:
    a head a program; Qwen3-Next: a block a program) — a change of
    admission that would move a cell's numbers fails here first."""
    import unittest.mock as mock

    from deeplearning4j_tpu.ops import pallas_kernels as pk

    b, _, t, d = shape
    with mock.patch("jax.default_backend", return_value="tpu"):
        assert att.choose_impl("auto", b, t, d, masked=False) == "flash"
    assert pk.pick_flash_blocks(t, d, jnp.bfloat16) == blocks
    assert pk._whole_head(t, blocks[0]) == (t == 1024)


def test_both_attention_layers_go_through_one_door(rng):
    """MultiHeadAttention and GatedAttention hand their heads to
    `ops.attention.attend` and to nothing else: whatever the door decides
    for a shape, it decides for both."""
    import unittest.mock as mock

    from deeplearning4j_tpu.nn.layers import GatedAttention

    x = jnp.asarray(rng.standard_normal((2, 16, 32)), jnp.float32)
    itype = it.recurrent(32, 16)
    seen = []

    def door(q, k, v, **kw):
        seen.append((q.shape, k.shape, v.shape, kw["causal"]))
        return att.sdpa(q, k, v, mask=kw.get("mask"), causal=kw["causal"])

    with mock.patch.object(att, "attend", side_effect=door):
        for layer in (MultiHeadAttention(n_heads=4, causal=True),
                      GatedAttention(n_heads=4, n_kv_heads=2, head_dim=8)):
            params = layer.init_params(jax.random.PRNGKey(0), itype)
            y, _ = layer.apply(params, x, state={}, train=False, rng=None)
            assert y.shape == x.shape
    assert seen == [((2, 4, 16, 8),) * 3 + (True,)] * 2


# ---------------------------------------------------------------------------
# remat 'full' keeps the flash forward's output and logsumexp, and a latent
# layer's q, k, v (REMAT_KEEP)
# ---------------------------------------------------------------------------
FLASH_B, FLASH_T, FLASH_F, FLASH_H, FLASH_DV = 2, 128, 32, 2, 16
LATENT_RANK, LATENT_NOPE, LATENT_ROPE = 16, 16, 8


@pytest.fixture(params=["latent", "latent_rope", "gated", "mha"])
def flash_stack(request, rng):
    """Two attention blocks, each behind `maybe_remat(., policy)`, whose heads
    `attend` hands to the flash kernels (interpreted here): `loss(policy)`
    is a function of (params, x), `block` one residual block."""
    import functools
    import unittest.mock as mock

    from deeplearning4j_tpu.nn.layers import GatedAttention, LatentAttention
    from deeplearning4j_tpu.parallel.layout import maybe_remat

    latent = dict(n_heads=FLASH_H, kv_rank=LATENT_RANK, nope_dim=LATENT_NOPE,
                  rope_dim=LATENT_ROPE, v_dim=FLASH_DV)
    layer = {
        # keys 192 = 128 + 64 and values 128, an eighth the size: kimi-linear's
        # (no positions) and kanana2's (the rope parts rotated)
        "latent": LatentAttention(**latent),
        "latent_rope": LatentAttention(rope_theta=10000.0, **latent),
        "gated": GatedAttention(n_heads=FLASH_H, n_kv_heads=1, head_dim=FLASH_DV),
        "mha": MultiHeadAttention(n_heads=FLASH_H, causal=True),
    }[request.param]
    itype = it.recurrent(FLASH_F, FLASH_T)
    params = [layer.init_params(jax.random.PRNGKey(i), itype) for i in range(2)]
    x = jnp.asarray(rng.standard_normal((FLASH_B, FLASH_T, FLASH_F)), jnp.float32)

    def block(p, h):
        return h + layer.apply(p, h, state={}, train=True, rng=None)[0]

    def loss(params, x, policy):
        for p in params:
            # a function object a trace: a checkpoint's jaxpr is cached by it
            x = maybe_remat(lambda p, h: block(p, h), policy)(p, x)
        return jnp.sum(x * x)

    with mock.patch.object(att, "choose_impl", return_value="flash"):
        yield (lambda policy: functools.partial(loss, policy=policy)), block, params, x


def _untagged(flash=True):
    """The flash forward rule and the latent layer as the commits before
    their tags had them: nothing named. `flash=False`: the layer's tag alone."""
    import contextlib
    import unittest.mock as mock

    from deeplearning4j_tpu.nn.layers import hybrid
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    stack = contextlib.ExitStack()
    for module in (hybrid, pk)[:1 + flash]:
        stack.enter_context(mock.patch.object(module, "checkpoint_name", lambda a, name: a))
    return stack


def _eqns(jaxpr, primitive):
    """Every equation of one primitive in a jaxpr, at any depth, in order."""
    found = []
    for e in jaxpr.eqns:
        if e.primitive.name == primitive:
            found.append(e)
        for inner in jax.core.jaxprs_in_params(e.params):
            found += _eqns(inner, primitive)
    return found


def _kernel_calls(jaxpr):
    """The family of every `pallas_call` in a jaxpr."""
    return [e.params["name"].split("_bh")[0] for e in _eqns(jaxpr, "pallas_call")]


def test_full_remat_calls_the_flash_forward_once_a_layer(flash_stack):
    """The gradient of two blocks under 'full' holds ONE forward kernel call
    a layer and one backward: the recompute drops the call whose two results
    are kept. Untagged, the forward kernel runs twice a layer."""
    loss, _, params, x = flash_stack

    def calls():
        jaxpr = jax.make_jaxpr(jax.grad(loss("full")))(params, x)
        return sorted(_kernel_calls(jaxpr.jaxpr))

    assert calls() == ["dl4j_flash_bwd"] * 2 + ["dl4j_flash_fwd"] * 2
    with _untagged():
        assert calls() == ["dl4j_flash_bwd"] * 2 + ["dl4j_flash_fwd"] * 4


def test_full_remat_gradient_is_the_unrematted_gradient(flash_stack):
    """One forward result used twice in place of two equal results: the
    gradient under 'full' is the gradient under 'none', bit for bit."""
    loss, _, params, x = flash_stack
    full = jax.jit(jax.grad(loss("full"), argnums=(0, 1)))(params, x)
    none = jax.jit(jax.grad(loss("none"), argnums=(0, 1)))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(full), jax.tree_util.tree_leaves(none)):
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_full_remat_saves_the_inputs_the_output_and_the_logsumexp(flash_stack, request, capsys):
    """What a checkpointed block keeps for its backward: its arguments, o
    [b, h, t, dv] and the logsumexp [b, h, t] in float32, around a latent
    layer also q, k [b, h, t, nope + rope] and v — and nothing else;
    untagged, its arguments alone."""
    from deeplearning4j_tpu.parallel.layout import maybe_remat

    _, block, params, x = flash_stack

    def kept():
        # a function object of its own: a checkpoint's trace is cached by it
        jax.ad_checkpoint.print_saved_residuals(
            maybe_remat(lambda p, h: block(p, h), "full"), params[0], x)
        lines = capsys.readouterr().out.strip().splitlines()
        assert any("from the argument h" in ln for ln in lines)
        # (a trace's own constants — the rotation's 0 / 1 matrix and masks — are no kept values)
        return sorted(ln.split()[0] for ln in lines
                      if "from the argument" not in ln and "from a constant" not in ln)

    o = f"f32[{FLASH_B},{FLASH_H},{FLASH_T},{FLASH_DV}]"
    lse = f"f32[{FLASH_B},{FLASH_H},{FLASH_T}]"
    qk = f"f32[{FLASH_B},{FLASH_H},{FLASH_T},{LATENT_NOPE + LATENT_ROPE}]"
    latent = "latent" in request.node.callspec.id
    assert kept() == sorted([o, lse] + [qk, qk, o] * latent)   # v has o's shape
    with _untagged():
        assert kept() == []


def test_full_remat_makes_a_latent_layers_q_k_v_once(flash_stack, request):
    """The gradient of two latent blocks under 'full' holds `x Wq` and
    `c Wkvb` once a layer forward — each with its two products backward —
    and not again in the recompute, nor the rotations' two products, and the
    key's concatenate once a layer; untagged, each of them twice. `x Wkva`
    stays in the recompute (the norm's backward reads c). Around the other
    two layers the latent layer's tag changes no count."""
    loss, _, params, x = flash_stack
    case = request.node.callspec.id

    def counts():
        jaxpr = jax.make_jaxpr(jax.grad(loss("full")))(params, x).jaxpr
        dots = _eqns(jaxpr, "dot_general")
        made = [tuple(e.outvars[0].aval.shape) for e in dots]
        wq = made.count((FLASH_B, FLASH_T, FLASH_H * (LATENT_NOPE + LATENT_ROPE)))
        wkvb = made.count((FLASH_B, FLASH_T, FLASH_H * (LATENT_NOPE + FLASH_DV)))
        wkva = made.count((FLASH_B, FLASH_T, LATENT_RANK + LATENT_ROPE))
        return len(dots), wq, wkvb, wkva, len(_eqns(jaxpr, "concatenate"))

    tagged = counts()
    with _untagged(flash=False):     # the same 2 + 2 kernel calls on both sides
        untagged = counts()
    if "latent" not in case:
        assert tagged == untagged
        return
    # x Wq, c Wkvb, x Wkva as made forward + in the recompute; the concatenates
    assert tagged[1:] == (2, 2, 4, 2) and untagged[1:] == (4, 4, 4, 4)
    gone = 4 if case == "latent_rope" else 2     # a layer: Wq, Wkvb (+ q's and kr's rotation)
    assert (tagged[0], untagged[0]) == {"latent_rope": (46, 54), "latent": (38, 42)}[case]
    assert untagged[0] - tagged[0] == 2 * gone


def test_the_flash_tag_lowers_to_nothing_outside_a_checkpoint(flash_stack):
    """No `jax.checkpoint` around it (remat 'none', every forward-only call):
    the step lowers to the untagged step's text, but for the number the
    module's symbol table hands a private function (`@_where_58` / `_57`)."""
    import re

    loss, block, params, x = flash_stack

    def lowered():
        texts = (jax.jit(jax.grad(loss("none"))).lower(params, x).as_text(),
                 jax.jit(lambda p, h: block(p, h)).lower(params[0], x).as_text())
        return [re.sub(r"@(\w+?)_\d+\b", r"@\1", t) for t in texts]

    tagged = lowered()
    assert "dl4j_remat_keep" not in "".join(tagged)
    with _untagged():
        assert lowered() == tagged


def test_full_remat_keeps_the_flash_results_under_a_data_mesh(flash_stack):
    """Under a two-device `data` mesh the kernels run inside
    `kernel_call.per_batch_shard`'s `shard_map`; the name is carried through
    it: one forward kernel call a layer there too, each on one device's row."""
    from deeplearning4j_tpu.parallel import MeshSpec, build_mesh

    loss, _, params, x = flash_stack

    def calls():
        jaxpr = jax.make_jaxpr(jax.grad(loss("full")))(params, x)
        assert "shard_map" in str(jaxpr)
        return sorted(_kernel_calls(jaxpr.jaxpr))

    with jax.set_mesh(build_mesh(MeshSpec(data=2), devices=jax.devices()[:2])):
        assert calls() == ["dl4j_flash_bwd"] * 2 + ["dl4j_flash_fwd"] * 2
        with _untagged():
            assert calls() == ["dl4j_flash_bwd"] * 2 + ["dl4j_flash_fwd"] * 4
        full, none = (jax.jit(jax.grad(loss(p)))(params, x) for p in ("full", "none"))
    for a, b in zip(jax.tree_util.tree_leaves(full), jax.tree_util.tree_leaves(none)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
