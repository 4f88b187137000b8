"""A state-space mixer with a scalar decay per head (Mamba-2, in its chunked
"SSD" form; ROADMAP R3, R4, R8). The block that places it behind a pre-norm
and a residual is `blocks.SubLayerBlock`.

BTF [batch, time, features], weights [n_in, n_out], like `hybrid.py`, whose
chunk-major layout, short convolution and row mapping this file uses as
they are (`to_chunks`, `conv_silu`, `from_chunks`, `over_row_groups`): on
a TPU the convolution of x [.., 128, 64] and of B | C [.., 128, 128] runs as
the kernel pair `dl4j_convsilu_fwd` / `dl4j_convsilu_bwd`, the 64-wide heads
with their tokens on the lanes (`ops/convsilu_kernels.py`), and the chunked
recurrence itself as the pair `dl4j_ssd_fwd` / `dl4j_ssd_bwd` wherever
`ops.delta.ssd_impl` admits the operands (`ops/ssd_kernels.py`: a chunk's
decays, scores and the carried state stay on the chip); `ssd_chunked` below
is the form everywhere else, and the tests' oracle.

  Mamba2Mixer   [z | x B C | dt] = u Win; [x B C] <- silu(causal depthwise
                conv + bias); x in H heads of P channels, B and C in G
                groups of N (head h reads group h // (H / G));
                dt <- softplus(dt + dt_bias); A = -exp(A_log). Per head, in
                float32, S [P x N] from 0:
                  S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t
                  y_t = S_t C_t + D x_t
                run in chunks (`ssd_chunked`, or its kernels); y <- group-wise
                RMS norm of y silu(z) (the gate BEFORE the norm, one mean a
                group of H / G heads); Wout. No bias but the convolution's.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.layers import hybrid as hy
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.ops import delta
from deeplearning4j_tpu.ops import linear as ops
from deeplearning4j_tpu.telemetry.trace import device_scope

F32 = jnp.float32


def _ssd_step(s, db):
    """The body of the scan over chunks: S' = d S + B with one decay a
    head. Emits the state the chunk STARTS from."""
    d, b_i = db
    return d[..., None, None] * s + b_i, s


def ssd_chunked(x, dt, a, b, c):
    """The scalar-decay state-space recurrence over chunks, chunk-major
    (`to_chunks`): x [n, r, h, cl, p], dt [n, r, h, cl] (after the
    softplus; 0 on a token that writes nothing and keeps the state), a [h]
    (negative), b and c [n, r, g, cl, s], all float32 -> (y [n, r, h, cl, p]
    without the skip, the states the chunks start from [n, r, h, p, s]).
    Head j reads group j // (h / g); b and c are never repeated to h heads:
    C B^T is computed once a group and every array of a head is held as
    [.., g, h / g, ..] beside it.

    With G_i the running sum of dt A within the chunk, token i of a chunk
    that starts from S reads
      y_i = sum_{j <= i} (C_i . B_j) exp(G_i - G_j) dt_j x_j + exp(G_i) S C_i
    and the chunk hands on S' = exp(G_last) S + sum_j exp(G_last - G_j)
    dt_j x_j (x) B_j: two batched products within the chunk, one for the
    chunk's own state, a scan over chunks that is one multiply-add of the
    state, one product to read it. Every exponent is <= 0. No solve; autodiff
    through the products and the scan gives the backward in chunks too."""
    mm = hy._mm
    n, r, h, cl, p = x.shape
    g, s = b.shape[2], b.shape[-1]

    def per_group(t):   # [n, r, h, ...] -> [n, r, g, h / g, ...]: no data moves
        return t.reshape((n, r, g, h // g) + t.shape[3:])

    x, dt = per_group(x), per_group(dt)
    gc = jnp.cumsum(dt * a.reshape(g, h // g, 1), axis=-1)         # [n, r, g, e, cl]
    i = jnp.arange(cl)
    lower = i[:, None] >= i[None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, gc[..., :, None] - gc[..., None, :], 0.0)), 0.0)
    cb = mm("nrgis,nrgjs->nrgij", c, b)[:, :, :, None]             # once a group
    xd = x * dt[..., None]
    y = mm("nrgeij,nrgejp->nrgeip", cb * decay, xd)
    b_all = mm("nrgejp,nrgjs->nrgeps", xd * jnp.exp(gc[..., -1:] - gc)[..., None], b)
    with device_scope("scan"):
        _, s_all = lax.scan(_ssd_step, jnp.zeros((r, h, p, s), F32),
                            (jnp.exp(gc[..., -1]).reshape(n, r, h), b_all.reshape(n, r, h, p, s)))
    y = y + jnp.exp(gc)[..., None] * mm("nrgis,nrgeps->nrgeip", c, per_group(s_all))
    return y.reshape(n, r, h, cl, p), s_all


@register_layer
@dataclass
class Mamba2Mixer(Layer):
    """The scalar-decay state-space mixer over [b, t, f] (see the module
    docstring). Win [f, 2 n_heads head_dim + 2 n_groups state_dim + n_heads]
    = [z | x | B | C | dt]; conv [conv_width, n_heads head_dim + 2 n_groups
    state_dim] with its bias.

    State `counters` (read once a fit into `telemetry.fit_log()` under
    `ssm`): `steps`, and float32 sums over the steps of the mean and of the
    smallest per-token decay exp(dt A) and of the largest |state| a chunk
    starts from."""

    n_heads: int = 64
    head_dim: int = 64
    n_groups: int = 8
    state_dim: int = 128
    conv_width: int = 4
    chunk: int = 128
    eps: float = 1e-5
    dt_min: float = 1e-3
    dt_max: float = 0.1
    dt_floor: float = 1e-4

    #: `hybrid.CORE_BYTES` for this layer: at 8192 tokens a row the
    #: convolution input [x | B | C] is 201 MB in float32; x, the rule's
    #: output, the chunk-start states and the cotangent of each 134 MB. With
    #: the rule as kernels nothing else of the rule reaches HBM; the XLA form
    #: also writes a chunk's decayed scores [n, h, 128, 128], 268 MB, for its
    #: backward
    CORE_BYTES = hy.CORE_BYTES

    def output_type(self, input_type):
        return input_type

    def _widths(self):
        if self.n_heads % self.n_groups:
            raise ValueError(f"n_groups={self.n_groups} must divide n_heads={self.n_heads}")
        return self.n_heads * self.head_dim, 2 * self.n_groups * self.state_dim

    def init_params(self, rng, input_type):
        f = input_type.size
        inner, bc = self._widths()
        r = jax.random.split(rng, 5)
        # dt log-uniform over [dt_min, dt_max], dt_bias its inverse softplus
        dt = jnp.exp(jax.random.uniform(r[2], (self.n_heads,), F32)
                     * (jnp.log(self.dt_max) - jnp.log(self.dt_min)) + jnp.log(self.dt_min))
        dt = jnp.maximum(dt, self.dt_floor)
        return {
            "Win": hy._w(self, r[0], (f, 2 * inner + bc + self.n_heads)),
            "conv": jax.random.uniform(r[1], (self.conv_width, inner + bc), F32,
                                       -1.0, 1.0) * self.conv_width ** -0.5,
            "conv_b": jnp.zeros((inner + bc,), F32),
            "A_log": jnp.log(jax.random.uniform(r[3], (self.n_heads,), F32, 1.0, 16.0)),
            "D": jnp.ones((self.n_heads,), F32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "norm": jnp.ones((inner,), F32),
            "Wout": hy._w(self, r[4], (inner, f)),
        }

    def init_state(self, input_type):
        return hy.decay_counters()

    def regularizable(self, params):
        return {k: v for k, v in params.items() if k.startswith("W")}

    def counter_summary(self, added):
        """Per-step means of the counters over a fit, under `ssm`."""
        return hy.decay_summary("ssm", added)

    def _core(self, params, t, x, bc, dt, z, mask=None):
        """Everything between the projections, for rows r of t tokens.
        Chunk-major (`to_chunks`) and in the projection's dtype: x and z
        [n, r, h, c, p], bc [n, r, 2 g, c, s], dt [n, r, h, c], mask
        [n, r, 1, c] -> ([r, t, h p], the step's counters). The short
        convolution, the decays, the recurrence, the skip, the gated norm
        — and the one re-tiling back, of the result in the projection's
        dtype."""
        h, p, g, cw = self.n_heads, self.head_dim, self.n_groups, self.conv_width
        inner = h * p
        with device_scope("conv"):
            x = hy.conv_silu(x, params["conv"][:, :inner].reshape(cw, h, 1, p),
                             params["conv_b"][:inner].reshape(h, 1, p))
            bc = hy.conv_silu(bc, params["conv"][:, inner:].reshape(cw, 2 * g, 1, -1),
                              params["conv_b"][inner:].reshape(2 * g, 1, -1))
        with device_scope("gates"):
            dt = jax.nn.softplus(dt.astype(F32) + params["dt_bias"][:, None])
            if mask is not None:  # a padded token writes nothing, keeps the state
                dt = dt * mask
            a = -jnp.exp(params["A_log"])
        with device_scope("rule"):
            # the kernel pair where `ops.delta.ssd_impl` admits it, else the XLA form
            rule = (x, dt, a, bc[:, :, :g], bc[:, :, g:])
            got = delta.ssd_chunks(*rule)
            y, states = ssd_chunked(*rule) if got is None else got
        with device_scope("norm_gate"):
            y = (y + params["D"][:, None, None] * x) * jax.nn.silu(z.astype(F32))
            # one mean a group of h / g heads' channels
            n, r, _, c, _ = y.shape
            y = y.reshape(n, r, g, h // g, c, p)
            y = y * lax.rsqrt(jnp.mean(y * y, axis=(3, 5), keepdims=True) + self.eps)
            y = y.reshape(n, r, h, c, p) * params["norm"].reshape(h, 1, p)
            y = y.astype(z.dtype)
        with device_scope("retile"):
            y = hy.from_chunks(y, t)
        with device_scope("counters"):
            stats = hy.decay_stats(jnp.exp(dt * a[:, None]), states)
        return y.reshape(y.shape[:2] + (-1,)), stats

    def apply(self, params, x, *, state, train, rng, mask=None):
        b, t, _ = x.shape
        h, p, g, s = self.n_heads, self.head_dim, self.n_groups, self.state_dim
        inner, bc = self._widths()
        with device_scope("proj"):
            zxbcdt = ops.dot(x, params["Win"])
        z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + bc], axis=-1)
        if mask is not None:  # a padded token enters no convolution window
            xbc = xbc * mask[..., None].astype(xbc.dtype)
        rows = hy.rows_at_a_time(b, t * (inner + bc) * 4, self.CORE_BYTES)
        # z goes along so that the gate is taken where the recurrence's output lies
        args = [(xbc[..., :inner], (h, p)), (xbc[..., inner:], (2 * g, s)),
                (dt, ()), (z, (h, p))]
        if mask is not None:
            args.append((mask.astype(F32)[..., None], ()))
        core = {k: params[k] for k in ("conv", "conv_b", "A_log", "D", "dt_bias", "norm")}
        y, stats = hy.over_row_groups(
            lambda *a: self._core(core, t, *a), args, rows, self.chunk)
        with device_scope("proj"):
            y = ops.dot(y.reshape(b, t, inner), params["Wout"])
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        with device_scope("counters"):
            return y, hy.count_decay(state, *stats) if train else state
