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
