"""A window in causal attention — query i sees the `window` keys i - window + 1
.. i, its own among them — through every formulation: `sdpa`, `blockwise`
and the flash kernels (interpreted here) against a ten-line masked softmax,
value and the gradients of q, k and v; a window of t or more IS the causal
call (bits, kernel name); no window leaves the kernels' names and the traced
call as they were; `flash_visits` walks every block the band touches and no
other, forward and backward alike, and leaves unmasked only blocks that lie
wholly inside the band; a block a program (traced bounds) walks the same band
as a head a program (static ones); ring attention refuses a window by name."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import attention as att
from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.ops import ring

F32 = jnp.float32
WINDOWS = [1, 64, 512, 4096]          # the last: t and more
BLOCKS = [128, 256, 512]
LENGTHS = [512, 1024]
H, D = 2, 32


def plain(q, k, v, window=None):
    """softmax over the keys 0 <= i - j < window, materialised."""
    t = q.shape[2]
    s = jnp.einsum("bhid,bhjd->bhij", q, k) * q.shape[-1] ** -0.5
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    keep = (back >= 0) & (back < (t if window is None else window))
    return jnp.einsum("bhij,bhjd->bhid", jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1), v)


def operands(t, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(k, (1, H, t, D), F32) for k in keys)


def value_and_grads(fn, q, k, v, do):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(do)


def agree(fn, t, window, atol=2e-5):
    q, k, v, do = operands(t)
    got = value_and_grads(fn, q, k, v, do)
    want = value_and_grads(lambda q, k, v: plain(q, k, v, window), q, k, v, do)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, atol=atol, rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("t", LENGTHS)
@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("form", ["sdpa", "blockwise"])
def test_xla_forms_with_a_window(form, window, t):
    fn = {"sdpa": lambda q, k, v: att.sdpa(q, k, v, causal=True, window=window),
          "blockwise": lambda q, k, v: att.blockwise(q, k, v, causal=True, window=window,
                                                     block_size=256)}[form]
    agree(fn, t, window)


def flash(window, blk):
    return lambda q, k, v: pk.flash_attention(q, k, v, True, None, blk, blk, True, window)


@pytest.mark.parametrize("t", LENGTHS)
@pytest.mark.parametrize("blk", BLOCKS)
@pytest.mark.parametrize("window", WINDOWS)
def test_flash_kernels_with_a_window(window, blk, t):
    agree(flash(window, blk), t, window)
    if window >= t:          # the causal call, bit for bit
        q, k, v, do = operands(t)
        got = value_and_grads(flash(window, blk), q, k, v, do)
        causal = value_and_grads(
            lambda q, k, v: pk.flash_attention(q, k, v, True, None, blk, blk, True), q, k, v, do)
        for a, b in zip(got, causal):
            assert (np.asarray(a) == np.asarray(b)).all()


@pytest.mark.parametrize("blocks", [(128, 128), (256, 256), (512, 512), (128, 256), (256, 128)])
@pytest.mark.parametrize("window", [1, 64, 300, 512])
def test_a_block_a_program_walks_the_same_band(window, blocks, monkeypatch):
    """The bounds traced (a q or key block a program, as at t 8192) where the
    cases above run a head a program with static ones."""
    monkeypatch.setattr(pk, "_whole_head", lambda t, blk: False)
    bq, bk = blocks
    agree(lambda q, k, v: pk.flash_attention(q, k, v, True, None, bq, bk, True, window),
          1024, window)


def kernel_names(fn, t=512):
    q, k, v, do = operands(t)
    text = str(jax.make_jaxpr(lambda q, k, v: value_and_grads(fn, q, k, v, do))(q, k, v))
    return sorted(set(re.findall(r"dl4j_flash_[a-z]+_[a-z0-9_]+", text)))


def test_the_window_is_in_the_kernels_names_only_when_there_is_one():
    assert kernel_names(flash(64, 128)) == [
        "dl4j_flash_bwd_bh2_t512_d32_w64_bq128_bk128_float32",
        "dl4j_flash_fwd_bh2_t512_d32_w64_bq128_bk128_float32"]
    todays = ["dl4j_flash_bwd_bh2_t512_d32_bq128_bk128_float32",
              "dl4j_flash_fwd_bh2_t512_d32_bq128_bk128_float32"]
    assert kernel_names(flash(None, 128)) == kernel_names(flash(512, 128)) == todays
    assert kernel_names(
        lambda q, k, v: pk.flash_attention(q, k, v, True, None, 128, 128, True)) == todays


def test_no_window_is_todays_call():
    """`window=None` through `attend`: the jaxpr a call without the argument
    traces to, at every implementation."""
    q, k, v, _ = operands(512)
    for impl in ("pallas", "blockwise", "sdpa"):
        with_none = jax.make_jaxpr(
            lambda q, k, v: att.attend(q, k, v, causal=True, impl=impl, window=None))(q, k, v)
        without = jax.make_jaxpr(
            lambda q, k, v: att.attend(q, k, v, causal=True, impl=impl))(q, k, v)
        assert str(with_none) == str(without), impl


@pytest.mark.parametrize("impl", ["pallas", "blockwise", "sdpa"])
def test_attend_hands_the_window_to_every_implementation(impl, monkeypatch):
    monkeypatch.setenv("DL4J_TPU_PALLAS", "1")
    agree(lambda q, k, v: att.attend(q, k, v, causal=True, impl=impl, window=64,
                                     block_size=128), 512, 64)


@pytest.mark.parametrize("bk", BLOCKS)
@pytest.mark.parametrize("bq", BLOCKS)
@pytest.mark.parametrize("t", [512, 1024, 2048])
def test_flash_visits_walk_the_band_and_nothing_else(t, bq, bk):
    back = np.arange(t)[:, None] - np.arange(t)[None, :]
    for window in (1, 2, 64, 127, 128, 129, 300, 512, 513, 1000, None):
        keep = (back >= 0) & (back < (t if window is None else window))
        blocks = keep.reshape(t // bq, bq, t // bk, bk)
        touched = {(a, b) for a in range(t // bq) for b in range(t // bk) if blocks[a, :, b].any()}
        inside = {(a, b) for a in range(t // bq) for b in range(t // bk) if blocks[a, :, b].all()}
        visits = pk.flash_visits(t, bq, bk, True, window)
        for walk in ("q_major", "k_major"):
            pairs = [(a, b) for a, b, _ in visits[walk]]
            assert len(pairs) == len(set(pairs)), (window, walk)
            assert set(pairs) == touched, (window, walk)
            assert {(a, b) for a, b, masked in visits[walk] if not masked} <= inside, (window, walk)
        if bq == bk and window is not None and window % bk == 0:
            # square blocks under a window of whole blocks: only the two edges are masked
            assert all(masked == ((a, b) not in inside) for a, b, masked in visits["q_major"])


def test_without_a_window_the_visits_are_todays():
    v = pk.flash_visits(1024, 256, 256, True)
    assert v == pk.flash_visits(1024, 256, 256, True, None)
    assert len(v["q_major"]) == len(v["k_major"]) == 10
    assert sum(masked for _, _, masked in v["q_major"]) == 4
    full = pk.flash_visits(1024, 256, 256, False)
    assert len(full["q_major"]) == 16 and not any(m for _, _, m in full["q_major"])


def test_band_fill_counts_the_plan():
    """Score elements inside the band over those in the blocks the shipped
    plan visits: the band 512 x 513 / 2 + (t - 512) x 512 whatever the plan."""
    band, visited = att.band_fill(8192, 128, jnp.bfloat16, 512)
    assert band == 512 * 513 // 2 + (8192 - 512) * 512
    bq, bk = pk.pick_flash_blocks(8192, 128, jnp.bfloat16)
    assert visited == len(pk.flash_visits(8192, bq, bk, True, 512)["q_major"]) * bq * bk
    assert (bq, bk) == (512, 512) and band / visited == pytest.approx(0.5, abs=0.005)
    whole = att.band_fill(1024, 64, F32, None)
    assert whole == att.band_fill(1024, 64, F32, 4096)              # a window of t or more
    assert whole[0] == 1024 * 1025 // 2
    assert att.band_fill(100, 64, F32, 10) == (10 * 11 // 2 + 90 * 10, 100 * 100)   # no plan: sdpa


def test_a_window_needs_causal_and_a_key():
    q, k, v, _ = operands(128)
    with pytest.raises(ValueError, match="causal"):
        att.sdpa(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="causal"):
        pk.flash_attention(q, k, v, False, None, 128, 128, True, 8)
    with pytest.raises(ValueError, match="window=0"):
        att.attend(q, k, v, causal=True, window=0)


def test_ring_attention_refuses_a_window_by_name():
    q, k, v, _ = operands(128)
    with pytest.raises(NotImplementedError, match="window=64"):
        ring.ring_attention_sharded(q, k, v, axis_name="seq", causal=True, window=64)
    with ring.sequence_parallel("seq"):
        with pytest.raises(NotImplementedError, match="ring attention has no window"):
            att.attend(q, k, v, causal=True, window=64)
