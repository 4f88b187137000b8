"""Pallas kernels vs XLA reference numerics (interpret mode on CPU) — the
helper-vs-builtin equivalence tests, mirroring the reference's
CuDNNGradientChecks / ValidateCudnnLSTM pattern (SURVEY.md §2.3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import attention as att
from deeplearning4j_tpu.ops.pallas_kernels import (
    _lstm_ref,
    flash_attention,
    lstm_scan,
)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_sdpa(self, rng, causal):
        b, h, t, d = 2, 3, 64, 16
        q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
        ref = att.sdpa(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal, None, 16, 16, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_gradients_match_sdpa(self, rng):
        b, h, t, d = 1, 2, 32, 8
        q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)

        g_ref = jax.grad(lambda *a: att.sdpa(*a, causal=True).sum(),
                         argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(
            lambda *a: flash_attention(*a, True, None, 8, 8, True).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g_ref, g_fl):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=1e-4, rtol=1e-4)

    def test_non_divisible_block_clamps(self, rng):
        q = jnp.asarray(rng.standard_normal((1, 1, 16, 8)), jnp.float32)
        out = flash_attention(q, q, q, False, None, 128, 128, True)
        ref = att.sdpa(q, q, q)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestLstmScan:
    def _inputs(self, rng, b=4, t=12, f=8, n=16):
        x = jnp.asarray(rng.standard_normal((b, t, f)), jnp.float32)
        W = jnp.asarray(rng.standard_normal((f, 4 * n)) * 0.2, jnp.float32)
        R = jnp.asarray(rng.standard_normal((n, 4 * n)) * 0.2, jnp.float32)
        bias = jnp.asarray(rng.standard_normal(4 * n) * 0.1, jnp.float32)
        zx = x @ W + bias
        h0 = jnp.zeros((b, n), jnp.float32)
        c0 = jnp.zeros((b, n), jnp.float32)
        return zx, R, h0, c0

    def test_matches_scan_reference(self, rng):
        zx, R, h0, c0 = self._inputs(rng)
        hs, hT, cT = lstm_scan(zx, R, h0, c0, 2, True)
        hs_r, hT_r, cT_r = _lstm_ref(zx, R, h0, c0)
        np.testing.assert_allclose(np.asarray(hs), np.asarray(hs_r),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(hT), np.asarray(hT_r), atol=1e-5)
        np.testing.assert_allclose(np.asarray(cT), np.asarray(cT_r), atol=1e-5)

    def test_nonzero_carry(self, rng):
        zx, R, _, _ = self._inputs(rng, b=2, t=5, n=8)
        h0 = jnp.asarray(rng.standard_normal((2, 8)) * 0.5, jnp.float32)
        c0 = jnp.asarray(rng.standard_normal((2, 8)) * 0.5, jnp.float32)
        hs, hT, cT = lstm_scan(zx, R, h0, c0, 2, True)
        hs_r, hT_r, cT_r = _lstm_ref(zx, R, h0, c0)
        np.testing.assert_allclose(np.asarray(hs), np.asarray(hs_r), atol=1e-5)

    def test_gradients_match_reference(self, rng):
        zx, R, h0, c0 = self._inputs(rng, b=2, t=6, n=8)

        def loss_k(zx, R):
            hs, hT, cT = lstm_scan(zx, R, h0, c0, 2, True)
            return (hs * hs).sum() + hT.sum()

        def loss_r(zx, R):
            hs, hT, cT = _lstm_ref(zx, R, h0, c0)
            return (hs * hs).sum() + hT.sum()

        gk = jax.grad(loss_k, argnums=(0, 1))(zx, R)
        gr = jax.grad(loss_r, argnums=(0, 1))(zx, R)
        for a, b in zip(gr, gk):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)


class TestPallasPeepholeLSTM:
    """Graves-peephole kernel: the GravesLSTM (BASELINE char-RNN) hot path.
    Mirrors ValidateCudnnLSTM.java: helper math vs reference scan, values
    and gradients."""

    def _inputs(self, rng, b=4, t=7, n=16):
        zx = jnp.asarray(rng.standard_normal((b, t, 4 * n)) * 0.2,
                         jnp.float32)
        R = jnp.asarray(rng.standard_normal((n, 4 * n)) * 0.2, jnp.float32)
        p = jnp.asarray(rng.standard_normal((3, n)) * 0.2, jnp.float32)
        h0 = jnp.asarray(rng.standard_normal((b, n)) * 0.3, jnp.float32)
        c0 = jnp.asarray(rng.standard_normal((b, n)) * 0.3, jnp.float32)
        return zx, R, p, h0, c0

    def test_matches_scan_reference(self, rng):
        from deeplearning4j_tpu.ops.pallas_kernels import (
            _lstm_peephole_ref,
            lstm_scan_peephole,
        )

        zx, R, p, h0, c0 = self._inputs(rng)
        out_k = lstm_scan_peephole(zx, R, p, h0, c0, 2, True)
        out_r = _lstm_peephole_ref(zx, R, p, h0, c0)
        for a, b in zip(out_k, out_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)

    def test_gradients_match_reference(self, rng):
        from deeplearning4j_tpu.ops.pallas_kernels import (
            _lstm_peephole_ref,
            lstm_scan_peephole,
        )

        zx, R, p, h0, c0 = self._inputs(rng, b=2, t=6, n=8)

        def loss(fn):
            def f(zx, R, p):
                hs, hT, cT = fn(zx, R, p, h0, c0)
                return (hs * hs).sum() + hT.sum() + (cT * cT).sum()
            return f

        gk = jax.grad(loss(lambda *a: lstm_scan_peephole(*a, 2, True)),
                      argnums=(0, 1, 2))(zx, R, p)
        gr = jax.grad(loss(_lstm_peephole_ref), argnums=(0, 1, 2))(zx, R, p)
        for a, b in zip(gr, gk):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("layer_cls", ["GravesLSTM",
                                           "GravesBidirectionalLSTM"])
    def test_layer_helper_on_off(self, rng, layer_cls):
        """Whole-layer equivalence with helpers enabled vs disabled (the
        CuDNNGradientChecks pattern) — covers the forward peephole kernel
        and the time-flipped reverse half of the bidirectional layer."""
        from deeplearning4j_tpu.nn import inputs as it
        from deeplearning4j_tpu.nn.layers import recurrent as rec
        from deeplearning4j_tpu.ops import pallas_kernels as pk

        layer = getattr(rec, layer_cls)(n_out=12)
        params = layer.init_params(jax.random.PRNGKey(0), it.recurrent(6, 9))
        x = jnp.asarray(rng.standard_normal((3, 9, 6)), jnp.float32)
        old = (pk.helpers_enabled, pk.lstm_helper_mode)
        try:
            pk.helpers_enabled = lambda: True
            pk.lstm_helper_mode = lambda: "forced"  # kernels are opt-in
            y_on, _ = layer.apply(params, x, state={}, train=False, rng=None)
            pk.helpers_enabled = lambda: False
            y_off, _ = layer.apply(params, x, state={}, train=False,
                                   rng=None)
        finally:
            pk.helpers_enabled, pk.lstm_helper_mode = old
        np.testing.assert_allclose(np.asarray(y_on), np.asarray(y_off),
                                   atol=1e-5, rtol=1e-5)


def _assert_helper_on_off_equal(rng, layer_cls: str):
    """Shared helper-toggle scaffold: layer output with the pallas fast
    path on vs off must agree."""
    from deeplearning4j_tpu.nn import inputs as it
    from deeplearning4j_tpu.nn.layers import recurrent as rec
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    layer = getattr(rec, layer_cls)(n_out=12)
    itype = it.recurrent(6, 9)
    params = layer.init_params(jax.random.PRNGKey(0), itype)
    x = jnp.asarray(rng.standard_normal((3, 9, 6)), jnp.float32)
    old = (pk.helpers_enabled, pk.lstm_helper_mode)
    try:
        pk.helpers_enabled = lambda: True
        pk.lstm_helper_mode = lambda: "forced"  # kernels are opt-in
        y_on, _ = layer.apply(params, x, state={}, train=False, rng=None)
        pk.helpers_enabled = lambda: False
        y_off, _ = layer.apply(params, x, state={}, train=False, rng=None)
    finally:
        pk.helpers_enabled, pk.lstm_helper_mode = old
    np.testing.assert_allclose(np.asarray(y_on), np.asarray(y_off),
                               atol=1e-5, rtol=1e-5)


def test_lstm_kernel_bf16_matches_reference(rng):
    """bf16 inputs (the mixed-precision policy's activation dtype) route
    through the time-major kernel variant and match the lax.scan reference
    within bf16 tolerance; f32 results are exactly unchanged."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        _lstm_peephole_ref,
        _lstm_ref,
        lstm_scan,
        lstm_scan_peephole,
    )

    B, T, N = 4, 7, 16
    zx = jnp.asarray(rng.standard_normal((B, T, 4 * N)) * 0.2, jnp.bfloat16)
    R = jnp.asarray(rng.standard_normal((N, 4 * N)) * 0.1, jnp.bfloat16)
    p = jnp.asarray(rng.standard_normal((3, N)) * 0.1, jnp.bfloat16)
    h0 = jnp.zeros((B, N), jnp.bfloat16)
    c0 = jnp.zeros((B, N), jnp.bfloat16)

    for got, want in zip(lstm_scan(zx, R, h0, c0, 2, True),
                         _lstm_ref(zx, R, h0, c0)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=5e-3)
    for got, want in zip(lstm_scan_peephole(zx, R, p, h0, c0, 2, True),
                         _lstm_peephole_ref(zx, R, p, h0, c0)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=5e-3)


class TestFusedBackward:
    """Round-3 fused backward kernels (cudnnRNNBackwardData/Weights +
    blockwise flash bwd roles): gradients must match the XLA reference
    formulations exactly, with the pallas bwd verified to actually run
    (not the over-budget fallback)."""

    def _spy(self, pk):
        import unittest.mock as mock

        orig = pk._lstm_bwd
        calls = []

        def spy(*a, **k):
            r = orig(*a, **k)
            calls.append(r is not None)
            return r

        return mock.patch.object(pk, "_lstm_bwd", side_effect=spy), calls

    @pytest.mark.parametrize("peephole", [False, True])
    def test_lstm_bwd_kernel_matches_reference(self, rng, peephole):
        from deeplearning4j_tpu.ops import pallas_kernels as pk

        b, t, n = 16, 10, 16
        zx = jnp.asarray(rng.standard_normal((b, t, 4 * n)) * 0.2,
                         jnp.float32)
        R = jnp.asarray(rng.standard_normal((n, 4 * n)) * 0.2, jnp.float32)
        p = jnp.asarray(rng.standard_normal((3, n)) * 0.2, jnp.float32)
        h0 = jnp.asarray(rng.standard_normal((b, n)) * 0.3, jnp.float32)
        c0 = jnp.asarray(rng.standard_normal((b, n)) * 0.3, jnp.float32)
        assert pk.pick_lstm_bwd_block(zx.shape, zx.dtype) >= 8

        if peephole:
            kf = lambda *a: pk.lstm_scan_peephole(*a, 8, True)
            rf = pk._lstm_peephole_ref
            args = (zx, R, p, h0, c0)
        else:
            kf = lambda *a: pk.lstm_scan(*a, 8, True)
            rf = pk._lstm_ref
            args = (zx, R, h0, c0)

        def loss(fn):
            def f(*a):
                hs, hT, cT = fn(*a)
                return (hs * hs).sum() + hT.sum() + (cT * cT).sum()
            return f

        nargs = tuple(range(len(args)))
        patch, calls = self._spy(pk)
        with patch:
            gk = jax.grad(loss(kf), argnums=nargs)(*args)
        assert calls == [True]  # the fused bwd ran, not the fallback
        gr = jax.grad(loss(rf), argnums=nargs)(*args)
        for a, b_ in zip(gr, gk):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=1e-4, rtol=1e-4)

    def test_lstm_bwd_kernel_bf16_time_major(self, rng):
        from deeplearning4j_tpu.ops import pallas_kernels as pk

        b, t, n = 16, 8, 16
        zx = jnp.asarray(rng.standard_normal((b, t, 4 * n)) * 0.2,
                         jnp.bfloat16)
        R = jnp.asarray(rng.standard_normal((n, 4 * n)) * 0.1, jnp.bfloat16)
        h0 = jnp.zeros((b, n), jnp.bfloat16)
        c0 = jnp.zeros((b, n), jnp.bfloat16)

        def loss(fn):
            def f(zx, R):
                hs, hT, cT = fn(zx, R)
                return ((hs * hs).sum() + hT.sum()).astype(jnp.float32)
            return f

        patch, calls = self._spy(pk)
        with patch:
            gk = jax.grad(loss(lambda zx, R: pk.lstm_scan(
                zx, R, h0, c0, 8, True)), argnums=(0, 1))(zx, R)
        assert calls == [True]
        gr = jax.grad(loss(lambda zx, R: pk._lstm_ref(zx, R, h0, c0)),
                      argnums=(0, 1))(zx, R)
        for a, b_ in zip(gr, gk):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b_, np.float32),
                                       atol=5e-2, rtol=5e-2)

    def test_lstm_bwd_ragged_batch_block(self, rng):
        """b % block != 0: the last grid program's padded rows are
        undefined block-padding and must NOT leak into the shared dR/dp
        accumulators (regression: b=12 with bb=8 produced NaN dR)."""
        from deeplearning4j_tpu.ops import pallas_kernels as pk

        b, t, n = 12, 6, 16
        zx = jnp.asarray(rng.standard_normal((b, t, 4 * n)) * 0.2,
                         jnp.float32)
        R = jnp.asarray(rng.standard_normal((n, 4 * n)) * 0.2, jnp.float32)
        p = jnp.asarray(rng.standard_normal((3, n)) * 0.2, jnp.float32)
        h0 = jnp.asarray(rng.standard_normal((b, n)) * 0.3, jnp.float32)
        c0 = jnp.asarray(rng.standard_normal((b, n)) * 0.3, jnp.float32)

        hs, hT, cT = pk.lstm_scan_peephole(zx, R, p, h0, c0, 8, True)
        g = (jnp.ones_like(hs), jnp.ones_like(hT), jnp.ones_like(cT))
        got = pk._lstm_bwd(zx, R, h0, c0, hs, g, interpret=True, p=p)
        assert got is not None  # bb=8 fits: grid = cdiv(12, 8) = 2
        _, vjp = jax.vjp(pk._lstm_peephole_ref, zx, R, p, h0, c0)
        ref = vjp(g)
        names = ("dzx", "dR", "dp", "dh0", "dc0")
        dzx, dR, dp, dh0, dc0 = got
        for name, a, b_ in zip(names, ref, (dzx, dR, dp, dh0, dc0)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=1e-4, rtol=1e-4,
                                       err_msg=name)

    @pytest.mark.parametrize("peephole", [False, True])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_masked_kernel_matches_reference(self, rng, peephole, dtype):
        """Round-3 mask support (MaskedReductionUtil semantics in-kernel):
        ragged lengths incl. zero-length and full-length rows, forward
        values AND all gradients vs the masked lax.scan reference — in
        both layouts (f32 batch-major, bf16 time-major with the
        batch-major [bb, t, 1] mask read)."""
        from deeplearning4j_tpu.ops import pallas_kernels as pk

        b, t, n = 16, 10, 16
        zx = jnp.asarray(rng.standard_normal((b, t, 4 * n)) * 0.2, dtype)
        R = jnp.asarray(rng.standard_normal((n, 4 * n)) * 0.2, dtype)
        p = jnp.asarray(rng.standard_normal((3, n)) * 0.2, dtype)
        h0 = jnp.asarray(rng.standard_normal((b, n)) * 0.3, dtype)
        c0 = jnp.asarray(rng.standard_normal((b, n)) * 0.3, dtype)
        lens = rng.integers(0, t + 1, b)
        lens[0], lens[1] = 0, t
        mask = jnp.asarray(
            (np.arange(t)[None, :] < lens[:, None]).astype(np.float32))

        if peephole:
            kf = lambda *a: pk.lstm_scan_peephole(*a, 8, True, mask)
            rf = lambda *a: pk._lstm_peephole_ref(*a, mask)
            args = (zx, R, p, h0, c0)
        else:
            kf = lambda *a: pk.lstm_scan(*a, 8, True, mask)
            rf = lambda *a: pk._lstm_ref(*a, None, mask)
            args = (zx, R, h0, c0)

        tol = 1e-5 if dtype == jnp.float32 else 5e-2
        for a, b_ in zip(rf(*args), kf(*args)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b_, np.float32),
                                       atol=tol, rtol=tol)
        # masked rows: zero output past their length, carried state
        hs_k = np.asarray(kf(*args)[0], np.float32)
        assert np.all(hs_k[0] == 0.0)  # zero-length row: all masked

        def loss(fn):
            def f(*a):
                hs, hT, cT = fn(*a)
                return ((hs * hs).sum() + hT.sum()
                        + (cT * cT).sum()).astype(jnp.float32)
            return f

        gtol = 1e-4 if dtype == jnp.float32 else 6e-2
        nargs = tuple(range(len(args)))
        patch, calls = self._spy(pk)
        with patch:
            gk = jax.grad(loss(kf), argnums=nargs)(*args)
        assert calls == [True]  # the masked fused bwd ran
        gr = jax.grad(loss(rf), argnums=nargs)(*args)
        for a, b_ in zip(gr, gk):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b_, np.float32),
                                       atol=gtol, rtol=gtol)

    def test_masked_layer_helper_on_off(self, rng):
        """Whole-layer equivalence with a ragged mask: masked sequences
        now ride the kernel instead of bailing to the scan path
        (VERDICT r2 weak #3)."""
        import unittest.mock as mock

        from deeplearning4j_tpu.nn import inputs as it
        from deeplearning4j_tpu.nn.layers import recurrent as rec
        from deeplearning4j_tpu.ops import pallas_kernels as pk

        for cls in ("GravesLSTM", "GravesBidirectionalLSTM"):
            layer = getattr(rec, cls)(n_out=12)
            params = layer.init_params(jax.random.PRNGKey(0),
                                       it.recurrent(6, 9))
            x = jnp.asarray(rng.standard_normal((16, 9, 6)), jnp.float32)
            lens = rng.integers(1, 10, 16)
            mask = jnp.asarray(
                (np.arange(9)[None, :] < lens[:, None]).astype(np.float32))
            calls = []
            orig = pk.lstm_scan_peephole

            def spy(*a, **k):
                calls.append(a[-1] is not None)  # mask argument present
                return orig(*a, **k)

            with mock.patch.object(pk, "helpers_enabled",
                                   return_value=True), \
                    mock.patch.object(pk, "lstm_helper_mode",
                                      return_value="forced"), \
                    mock.patch.object(pk, "lstm_scan_peephole",
                                      side_effect=spy):
                y_on, _ = layer.apply(params, x, state={}, train=False,
                                      rng=None, mask=mask)
            assert calls and all(calls), cls
            with mock.patch.object(pk, "helpers_enabled",
                                   return_value=False):
                y_off, _ = layer.apply(params, x, state={}, train=False,
                                       rng=None, mask=mask)
            np.testing.assert_allclose(np.asarray(y_on), np.asarray(y_off),
                                       atol=1e-5, rtol=1e-5,
                                       err_msg=cls)

    def test_lstm_bwd_over_budget_falls_back(self, rng):
        """A shape whose bwd block cannot fit VMEM must use the
        XLA-recompute vjp and still produce correct gradients."""
        from deeplearning4j_tpu.ops import pallas_kernels as pk

        b, t, n = 4, 6, 8  # b < 8: no aligned block
        assert pk.pick_lstm_bwd_block((b, t, 4 * n), jnp.float32) == 0
        zx = jnp.asarray(rng.standard_normal((b, t, 4 * n)) * 0.2,
                         jnp.float32)
        R = jnp.asarray(rng.standard_normal((n, 4 * n)) * 0.2, jnp.float32)
        h0 = jnp.zeros((b, n), jnp.float32)
        c0 = jnp.zeros((b, n), jnp.float32)

        def lk(zx, R):
            hs, hT, cT = lstm_scan(zx, R, h0, c0, 2, True)
            return (hs * hs).sum()

        def lr(zx, R):
            hs, hT, cT = _lstm_ref(zx, R, h0, c0)
            return (hs * hs).sum()

        gk = jax.grad(lk, argnums=(0, 1))(zx, R)
        gr = jax.grad(lr, argnums=(0, 1))(zx, R)
        for a, b_ in zip(gr, gk):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("plan", [
        (16, 16),   # bq = bk, a head a program (4 blocks)
        (8, 16),    # bq < bk
        (16, 8),    # bq > bk
        (64, 64),   # one block
        (4, 4),     # 16 blocks: a q block / a key block a program
        (4, 8),     # forward a block a program, backward a head
        (8, 4),     # the other way round
    ], ids=lambda p: f"{p[0]}x{p[1]}")
    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_bwd_random_cotangent(self, rng, causal, plan, dtype):
        """out and dq/dk/dv from the blockwise kernels vs the sdpa vjp
        under a random (not all-ones) output cotangent, over square and
        rectangular plans and both ways a head is cut into programs."""
        b, h, t, d = 2, 2, 64, 16
        q, k, v = (jnp.asarray(rng.standard_normal((b, h, t, d)) * 0.5,
                               dtype) for _ in range(3))
        co = jnp.asarray(rng.standard_normal((b, h, t, d)), dtype)
        bq, bk = plan

        def both(f, *a):
            out, vjp = jax.vjp(f, *a[:3])
            return (out,) + vjp(a[3].astype(out.dtype))

        got = both(lambda q, k, v: flash_attention(q, k, v, causal, None,
                                                   bq, bk, True), q, k, v, co)
        want = both(lambda q, k, v: att.sdpa(q, k, v, causal=causal),
                    *(a.astype(jnp.float32) for a in (q, k, v, co)))
        # float32: the tolerance this test has always had; bf16: its
        # rounding of the results (2^-8 of values of order 1..4)
        tol = 2e-4 if dtype == jnp.float32 else 3e-2
        for a, b_ in zip(want, got):
            assert b_.dtype == dtype
            np.testing.assert_allclose(np.asarray(a),
                                       np.asarray(b_, np.float32),
                                       atol=tol, rtol=tol)

    def test_lstm_kernels_are_opt_in(self, rng):
        """Default policy: the measured-slower LSTM kernel path stays off
        until DL4J_TPU_PALLAS_LSTM opts in."""
        import os
        import unittest.mock as mock

        from deeplearning4j_tpu.ops import pallas_kernels as pk

        env = dict(os.environ)
        env.pop("DL4J_TPU_PALLAS_LSTM", None)
        with mock.patch.dict(os.environ, env, clear=True):
            assert pk.lstm_helper_mode() == "auto"
        with mock.patch.dict(os.environ, {"DL4J_TPU_PALLAS_LSTM": "1"}):
            assert pk.lstm_helper_mode() == "forced"


def test_long_sequence_falls_back_to_scan(rng):
    """Sequences whose minimum batch block exceeds the VMEM budget must
    fall through to the lax.scan path instead of failing Mosaic compile
    (regression: a 2048-step GravesLSTM previously crashed on TPU)."""
    import unittest.mock as mock

    from deeplearning4j_tpu.nn import inputs as it
    from deeplearning4j_tpu.nn.layers import recurrent as rec
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    layer = rec.GravesLSTM(n_out=64)
    params = layer.init_params(jax.random.PRNGKey(0), it.recurrent(8, 2048))
    x = jnp.asarray(rng.standard_normal((2, 2048, 8)), jnp.float32)
    calls = []
    with mock.patch.object(pk, "helpers_enabled", return_value=True), \
            mock.patch.object(pk, "lstm_helper_mode",
                              return_value="forced"), \
            mock.patch.object(
                pk, "lstm_scan_peephole",
                side_effect=lambda *a, **k: calls.append(1)):
        y, _ = layer.apply(params, x, state={}, train=False, rng=None)
    assert y.shape == (2, 2048, 64)
    assert calls == []  # over budget: the kernel was never invoked


def test_pick_lstm_block_properties():
    """The kernel-owned block picker: 8-aligned blocks within the VMEM
    budget, 0 (= use lax.scan) when even the minimum block cannot fit."""
    from deeplearning4j_tpu.ops.pallas_kernels import pick_lstm_block

    assert pick_lstm_block((64, 64, 1024), jnp.float32) == 16  # bench shape
    assert pick_lstm_block((64, 320, 512), jnp.bfloat16) % 8 == 0
    assert pick_lstm_block((16, 2048, 1024), jnp.float32) == 0  # long seq
    assert pick_lstm_block((8, 1024, 384), jnp.float32) == 0  # 12MB edge
    assert pick_lstm_block((2, 10, 64), jnp.float32) == 0  # sub-minimum b


def test_pick_flash_blocks_properties():
    """The block picker (pick_flash_blocks): a whole-sequence block at
    t <= 512, square blocks of 256 up to t 2048 and of 512 above (PERF.md
    section 6, PR 30), always dividing t, falling down the candidate list
    for odd lengths."""
    from deeplearning4j_tpu.ops.pallas_kernels import pick_flash_blocks

    assert pick_flash_blocks(512, 64, jnp.bfloat16) == (512, 512)
    assert pick_flash_blocks(256, 64, jnp.bfloat16) == (256, 256)
    assert pick_flash_blocks(1024, 64, jnp.bfloat16) == (256, 256)
    assert pick_flash_blocks(1024, 64, jnp.float32) == (256, 256)
    assert pick_flash_blocks(2048, 64, jnp.bfloat16) == (256, 256)
    assert pick_flash_blocks(4096, 64, jnp.bfloat16) == (512, 512)
    assert pick_flash_blocks(8192, 256, jnp.bfloat16) == (512, 512)
    assert pick_flash_blocks(1280, 64, jnp.bfloat16) == (256, 256)
    assert pick_flash_blocks(4224, 64, jnp.bfloat16) == (128, 128)  # 33*128
    bq, bk = pick_flash_blocks(640, 64, jnp.float32)  # 640 = 5*128
    assert 640 % bq == 0 and 640 % bk == 0
    assert pick_flash_blocks(96, 64, jnp.float32) == (96, 96)  # one block
    with pytest.raises(ValueError, match="t % 128"):
        pick_flash_blocks(200, 64, jnp.float32)  # would drop rows


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t, plan", [
    (t, (bq, bk)) for t in (128, 384, 1024, 2048)
    for bq, bk in ((128, 128), (64, 128), (128, 64), (256, 512), (512, 256),
                   (256, 256), (128, 384), (384, 128), (2048, 512), (t, t))
    if t % bq == 0 and t % bk == 0],
    ids=lambda v: v if isinstance(v, int) else f"{v[0]}x{v[1]}")
def test_flash_visits_cover_the_triangle(t, plan, causal):
    """The (q block, key block) pairs the kernels walk — their own loop
    bounds, listed by `flash_visits` — against the triangle: no pair
    wholly in the future is visited, exactly the pairs the diagonal
    crosses are masked, and every visible element lies in exactly one
    visited pair; both for the kernel that walks key blocks (forward) and
    for the one that walks q blocks (backward)."""
    from deeplearning4j_tpu.ops.pallas_kernels import flash_visits

    bq, bk = plan
    rows, cols = np.arange(t)[:, None], np.arange(t)[None, :]
    visible = (cols <= rows) if causal else np.ones((t, t), bool)
    for order, pairs in flash_visits(t, bq, bk, causal).items():
        assert len(set((qi, kj) for qi, kj, _ in pairs)) == len(pairs), order
        seen = np.zeros((t, t), np.int32)
        for qi, kj, masked in pairs:
            block = visible[qi * bq:(qi + 1) * bq, kj * bk:(kj + 1) * bk]
            assert block.any(), (order, qi, kj, "wholly in the future")
            assert masked == (not block.all()), (order, qi, kj, masked)
            seen[qi * bq:(qi + 1) * bq, kj * bk:(kj + 1) * bk] += 1
        assert (seen[visible] == 1).all(), order


class TestChunkedLSTM:
    """Round-5 time-chunked LSTM kernels (lstm_scan_chunked): the long-t
    regime the full-resident kernels could not reach. Multi-chunk grids
    forced with small tc; CuDNNGradientChecks equivalence vs the
    lax.scan reference in values and gradients."""

    def _data(self, rng, b=8, t=48, n=16, dtype=jnp.float32):
        zx = jnp.asarray(rng.standard_normal((b, t, 4 * n)) * 0.2, dtype)
        R = jnp.asarray(rng.standard_normal((n, 4 * n)) * 0.05, dtype)
        h0 = jnp.asarray(rng.standard_normal((b, n)) * 0.1, dtype)
        c0 = jnp.asarray(rng.standard_normal((b, n)) * 0.1, dtype)
        return zx, R, h0, c0

    @pytest.mark.parametrize("masked", [False, True])
    def test_matches_reference_and_grads(self, rng, masked):
        from deeplearning4j_tpu.ops import pallas_kernels as pk

        zx, R, h0, c0 = self._data(rng)
        mk = None
        if masked:
            m = np.ones((8, 48), np.float32)
            m[0, 30:] = 0.0
            m[3, :5] = 0.0
            mk = jnp.asarray(m)
        hs, hT, cT = pk.lstm_scan_chunked(zx, R, h0, c0, 8, 16, True, mk)
        hs_r, hT_r, cT_r = pk._lstm_ref(zx, R, h0, c0, None, mk)
        np.testing.assert_allclose(np.asarray(hs), np.asarray(hs_r),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(hT), np.asarray(hT_r),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(cT), np.asarray(cT_r),
                                   atol=1e-6)

        def loss(fn):
            def f(zx, R, h0, c0):
                hs, hT, cT = fn(zx, R, h0, c0)
                w = (jnp.arange(hs.size, dtype=jnp.float32)
                     .reshape(hs.shape) / hs.size)
                return (hs * w).sum() + (hT * hT).sum() + cT.sum()
            return f

        gk = jax.grad(loss(lambda *a: pk.lstm_scan_chunked(
            *a, 8, 16, True, mk)), argnums=(0, 1, 2, 3))(zx, R, h0, c0)
        gr = jax.grad(loss(lambda *a: pk._lstm_ref(*a, None, mk)),
                      argnums=(0, 1, 2, 3))(zx, R, h0, c0)
        for a, e in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                       atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("masked", [False, True])
    def test_peephole_matches_reference_and_grads(self, rng, masked):
        """Peephole x mask is the richest bwd interaction: masked steps
        carry c through, so the recomputed zo sees the CARRIED c_new
        while peephole terms (po*dzo, pi*dzi + pf*dzf) ride the same
        passthrough — reachable in production via a masked Graves LSTM
        at f32 t >= 1024 (auto-admitted)."""
        from deeplearning4j_tpu.ops import pallas_kernels as pk

        zx, R, h0, c0 = self._data(rng)
        p = jnp.asarray(rng.standard_normal((3, 16)) * 0.1, jnp.float32)
        mk = None
        if masked:
            m = np.ones((8, 48), np.float32)
            m[1, 25:] = 0.0
            m[6, :12] = 0.0
            mk = jnp.asarray(m)
        hs, hT, cT = pk.lstm_scan_chunked_peephole(zx, R, p, h0, c0, 8,
                                                   16, True, mk)
        hs_r, hT_r, cT_r = pk._lstm_peephole_ref(zx, R, p, h0, c0, mk)
        np.testing.assert_allclose(np.asarray(hs), np.asarray(hs_r),
                                   atol=1e-6)

        def loss(fn):
            def f(zx, R, p):
                hs, hT, cT = fn(zx, R, p)
                return (hs * hs).sum() + hT.sum() + cT.sum()
            return f

        gk = jax.grad(loss(lambda zx, R, p: pk.lstm_scan_chunked_peephole(
            zx, R, p, h0, c0, 8, 16, True, mk)), argnums=(0, 1, 2))(zx, R, p)
        gr = jax.grad(loss(lambda zx, R, p: pk._lstm_peephole_ref(
            zx, R, p, h0, c0, mk)), argnums=(0, 1, 2))(zx, R, p)
        for a, e in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                       atol=1e-5, rtol=1e-5)

    def test_bf16_time_major_layout(self, rng):
        from deeplearning4j_tpu.ops import pallas_kernels as pk

        zx, R, h0, c0 = self._data(rng, dtype=jnp.bfloat16)
        hs, hT, cT = pk.lstm_scan_chunked(zx, R, h0, c0, 8, 16, True)
        hs_r, _, _ = pk._lstm_ref(zx, R, h0, c0)
        np.testing.assert_allclose(
            np.asarray(hs.astype(jnp.float32)),
            np.asarray(hs_r.astype(jnp.float32)), atol=2e-2)

    def test_pick_lstm_chunk_properties(self):
        from deeplearning4j_tpu.ops.pallas_kernels import pick_lstm_chunk

        got = pick_lstm_chunk((8, 1024, 1024), jnp.float32)
        assert got is not None
        bb, tc = got
        assert 8 % bb == 0 or bb <= 8
        assert 1024 % tc == 0
        # huge n: nothing fits even at the smallest block
        assert pick_lstm_chunk((8, 1024, 4 * 16384), jnp.float32) is None

    def test_layer_auto_admission_long_t(self, rng):
        """The LSTM layer takes the chunked kernel AUTOMATICALLY for f32
        t >= 1024 (the measured-win regime) — whole-layer equivalence
        with helpers off."""
        from deeplearning4j_tpu.nn import inputs as it
        from deeplearning4j_tpu.nn.layers import recurrent as rec
        from deeplearning4j_tpu.ops import pallas_kernels as pk

        layer = rec.LSTM(n_out=16)
        params = layer.init_params(jax.random.PRNGKey(0),
                                   it.recurrent(8, 1024))
        x = jnp.asarray(rng.standard_normal((8, 1024, 8)), jnp.float32)
        old = pk.helpers_enabled
        try:
            pk.helpers_enabled = lambda: True  # auto path, no LSTM opt-in
            y_on, _ = layer.apply(params, x, state={}, train=False,
                                  rng=None)
            pk.helpers_enabled = lambda: False
            y_off, _ = layer.apply(params, x, state={}, train=False,
                                   rng=None)
        finally:
            pk.helpers_enabled = old
        np.testing.assert_allclose(np.asarray(y_on), np.asarray(y_off),
                                   atol=1e-5, rtol=1e-5)


class TestBnActEpilogue:
    """Fused conv-bn-relu epilogue (bn_act) vs the XLA reference —
    the DL4J_TPU_PALLAS_CONVBN admission contract (docs/PERFORMANCE.md)."""

    def _inputs(self, rng, shape=(2, 4, 4, 8)):
        from deeplearning4j_tpu.ops import pallas_kernels as pk

        x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        c = shape[-1]
        scale = jnp.asarray(rng.standard_normal(c) * 0.1 + 1.0, jnp.float32)
        shift = jnp.asarray(rng.standard_normal(c) * 0.1, jnp.float32)
        br = pk.pick_bn_block(shape, jnp.float32)
        assert br > 0
        return x, scale, shift, br

    @pytest.mark.parametrize("act", ["relu", "identity"])
    def test_forward_matches_reference(self, rng, act):
        from deeplearning4j_tpu.ops import pallas_kernels as pk

        x, scale, shift, br = self._inputs(rng)
        out = pk.bn_act(x, scale, shift, act, br, True)
        ref = pk.bn_act_reference(x, scale, shift, act)
        assert out.dtype == x.dtype
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6, rtol=1e-6)

    def test_gradients_match_reference(self, rng):
        from deeplearning4j_tpu.ops import pallas_kernels as pk

        x, scale, shift, br = self._inputs(rng)

        def k_loss(x, s, h):
            return (pk.bn_act(x, s, h, "relu", br, True) ** 2).sum()

        def r_loss(x, s, h):
            return (pk.bn_act_reference(x, s, h, "relu") ** 2).sum()

        gk = jax.grad(k_loss, argnums=(0, 1, 2))(x, scale, shift)
        gr = jax.grad(r_loss, argnums=(0, 1, 2))(x, scale, shift)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)

    def test_block_picker_constraints(self):
        from deeplearning4j_tpu.ops import pallas_kernels as pk

        # rows must divide by the block, channels by 8
        assert pk.pick_bn_block((2, 4, 4, 8), jnp.float32) > 0
        assert pk.pick_bn_block((2, 4, 4, 7), jnp.float32) == 0
        assert pk.pick_bn_block((3, 5, 5, 8), jnp.float32) in (0, 5 * 5 * 3)
        # VMEM budget: a huge channel width forces smaller (or no) blocks
        br = pk.pick_bn_block((8, 64, 64, 8192), jnp.float32)
        assert 2 * br * 8192 * 4 <= 4 * 2 ** 20

    def test_batchnorm_layer_gated_path_matches(self, rng, monkeypatch):
        """End-to-end through nn/layers/normalization.BatchNorm: the
        forced gate swaps the epilogue implementation, never the
        numbers (float-rounding tolerance)."""
        from deeplearning4j_tpu.nn import inputs as it
        from deeplearning4j_tpu.nn.layers import normalization as nm
        from deeplearning4j_tpu.ops import pallas_kernels as pk

        layer = nm.BatchNorm(activation="relu")
        itype = it.convolutional(4, 4, 8)
        params = layer.init_params(jax.random.PRNGKey(0), itype)
        state = layer.init_state(itype)
        x = jnp.asarray(rng.standard_normal((2, 4, 4, 8)), jnp.float32)
        monkeypatch.delenv("DL4J_TPU" "_PALLAS_CONVBN", raising=False)
        y_off, _ = layer.apply(params, x, state=state, train=True,
                               rng=jax.random.PRNGKey(1))
        monkeypatch.setenv("DL4J_TPU" "_PALLAS_CONVBN", "1")
        monkeypatch.setattr(pk, "helpers_enabled", lambda: True)
        y_on, _ = layer.apply(params, x, state=state, train=True,
                              rng=jax.random.PRNGKey(1))
        np.testing.assert_allclose(np.asarray(y_on), np.asarray(y_off),
                                   atol=1e-6, rtol=1e-6)
