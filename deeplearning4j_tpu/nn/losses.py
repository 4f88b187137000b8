"""Loss function registry (ND4J `ILossFunction` surface, SURVEY.md §2.11).

Every loss is a pure function
    loss(labels, preactivations, activation_fn, mask, weights) -> (scalar, per_example)
returning both the reduced scalar score (mean over examples, matching DL4J's
`computeScore(..., average=true)`) and the per-example array (DL4J
`computeScoreArray`, used by e.g. EvaluativeListener and VAE reconstruction
probabilities).

DL4J's ILossFunction also exposes `computeGradient` (hand-derived dL/dPreOut);
here gradients come from `jax.grad` through these very functions, which is the
point of the TPU-first redesign (SURVEY.md §7 table, row 1).

Masking semantics: a mask of shape broadcastable to the per-example score
zeroes masked entries and the mean divides by the *active* count — this mirrors
DL4J's masked score averaging (LossUtil / MaskedReductionUtil).

Label weights (per-output-column) mirror DL4J's constructor-time weights on
LossMCXENT / LossBinaryXENT etc.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Union

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops import kernel_call
from deeplearning4j_tpu.ops import linear as ops
from deeplearning4j_tpu.telemetry.trace import device_scope

EPS = 1e-7

# loss_fn(labels, output_activations) -> per-element loss, same shape as labels
_REGISTRY: Dict[str, Callable] = {}


def register(name: str):
    def deco(f):
        _REGISTRY[name.lower()] = f
        return f

    return deco


def get(name_or_fn: Union[str, Callable]) -> Callable:
    if callable(name_or_fn):
        return name_or_fn
    key = str(name_or_fn).lower().replace("lossfunction.", "")
    aliases = {
        "negativeloglikelihood": "mcxent",
        "reconstruction_crossentropy": "xent",
        "squared_loss": "mse",
    }
    key = aliases.get(key, key)
    if key not in _REGISTRY:
        raise ValueError(f"Unknown loss '{name_or_fn}'. Known: {sorted(_REGISTRY)}")
    return _REGISTRY[key]


def names():
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# Elementwise losses: (labels, y) -> per-element loss. `y` is the *activated*
# output. Softmax-CE is special-cased below for numerical stability.
# ---------------------------------------------------------------------------


@register("mse")
def mse(labels, y):
    d = y - labels
    return d * d


@register("l2")
def l2(labels, y):
    # DL4J LossL2 = sum of squared errors (no 1/n); same elementwise form as MSE,
    # differing only in reduction (handled in compute()).
    d = y - labels
    return d * d


@register("l1")
def l1(labels, y):
    return jnp.abs(y - labels)


@register("mae")
def mae(labels, y):
    return jnp.abs(y - labels)


@register("xent")
def xent(labels, y):
    """Binary cross-entropy on sigmoid (or any (0,1)) outputs."""
    yc = jnp.clip(y, EPS, 1.0 - EPS)
    return -(labels * jnp.log(yc) + (1.0 - labels) * jnp.log1p(-yc))


@register("mcxent")
def mcxent(labels, y):
    """Multi-class cross-entropy on probabilities: -sum t*log(p)."""
    yc = jnp.clip(y, EPS, 1.0)
    return -labels * jnp.log(yc)


@register("kl_divergence")
@register("kld")
def kld(labels, y):
    lc = jnp.clip(labels, EPS, 1.0)
    yc = jnp.clip(y, EPS, 1.0)
    return labels * (jnp.log(lc) - jnp.log(yc))


@register("poisson")
def poisson(labels, y):
    yc = jnp.clip(y, EPS, None)
    return yc - labels * jnp.log(yc)


@register("mape")
def mape(labels, y):
    return 100.0 * jnp.abs((y - labels) / jnp.clip(jnp.abs(labels), EPS, None))


@register("msle")
def msle(labels, y):
    d = jnp.log1p(jnp.clip(y, -1 + EPS, None)) - jnp.log1p(
        jnp.clip(labels, -1 + EPS, None)
    )
    return d * d


@register("hinge")
def hinge(labels, y):
    # labels in {-1, +1} (DL4J converts {0,1} -> {-1,1} internally; we accept both)
    t = jnp.where(labels <= 0, -1.0, 1.0)
    return jnp.maximum(0.0, 1.0 - t * y)


@register("squared_hinge")
def squared_hinge(labels, y):
    h = hinge(labels, y)
    return h * h


@register("cosine_proximity")
def cosine_proximity(labels, y):
    # per-row loss = -cos_sim(labels, y); rows are the last axis
    num = jnp.sum(labels * y, axis=-1, keepdims=True)
    den = jnp.linalg.norm(labels, axis=-1, keepdims=True) * jnp.linalg.norm(
        y, axis=-1, keepdims=True
    )
    cos = num / jnp.clip(den, EPS, None)
    return -cos * jnp.ones_like(y) / y.shape[-1]  # spread over row for shape parity


@register("expll")
def expll(labels, y):
    """Exponential log-likelihood (legacy DL4J LossFunction.EXPLL)."""
    yc = jnp.clip(y, EPS, None)
    return yc - labels * jnp.log(yc)


@register("wasserstein")
def wasserstein(labels, y):
    return labels * y


# ---------------------------------------------------------------------------
# Score computation with masking/weights — the ILossFunction.computeScore
# contract.
# ---------------------------------------------------------------------------


def is_class_index(labels, preout_ndim: int) -> bool:
    """True for integer class labels: an integer array with one axis fewer
    than the pre-activations ([b] for [b, c], [b, t] for [b, t, c]). Dense
    (one-hot or soft) labels are floating point with the class axis."""
    return (jnp.issubdtype(jnp.result_type(labels), jnp.integer)
            and jnp.ndim(labels) == preout_ndim - 1)


def _head_logits(xb, w, b):
    """The head's logits of a block of rows, in the compute dtype."""
    z = ops.dot(xb, w)
    return z if b is None else ops.bias_add(z, b)


def _row_block(n: int, block_rows: int = 2048) -> Optional[int]:
    """The rows a visit of the row-blocked head takes: the largest of
    `block_rows`, 1024, .., 128 that divides n into more than one block;
    None -> all n rows in one."""
    return next((c for c in (block_rows, 1024, 512, 256, 128)
                 if c < n and n % c == 0), None)


def sparse_xent_rows(x, w, b, labels, block_rows: int = 2048):
    """Per-row softmax cross-entropy of the linear head x [n, f] @ w [f, c]
    (+ b) against integer labels [n], in float32, `block_rows` rows at a
    time: neither an [n, c] label array nor the whole [n, c] logits exist.
    Differentiated as it stands (a cotangent a row), its backward recomputes
    a block's logits instead of keeping them: a fourth product of head size.
    The fits do not pay it — they score through `sparse_xent_weighted`,
    whose value without a gradient this is; what still differentiates this
    one is a caller that needs per-row cotangents."""
    def rows(xb, lb):
        z = _head_logits(xb, w, b).astype(jnp.float32)
        picked = jnp.take_along_axis(z, lb[:, None].astype(jnp.int32), axis=-1)[:, 0]
        return jax.nn.logsumexp(z, axis=-1) - picked

    n = x.shape[0]
    block = _row_block(n, block_rows)
    if block is None:
        return rows(x, labels)
    per = jax.lax.map(lambda xl: jax.checkpoint(rows)(*xl),
                      (x.reshape(n // block, block, -1),
                       labels.reshape(n // block, block)))
    return per.reshape(n)


@jax.custom_vjp
def _weighted_xent(x, w, b, labels, row_weights):
    ce = sparse_xent_rows(x, w, b, labels)
    return jnp.sum(row_weights * ce), ce


def _weighted_xent_fwd(x, w, b, labels, row_weights):
    """The blocks of `sparse_xent_rows`, each visited ONCE: while a block's
    logits z are there, d(sum)/dz = weight (softmax(z) - one-hot) is too, so
    the block's dx and its share of dw and db are made in the same visit —
    the three products of head size the mathematics asks for, and nothing
    for the backward to recompute. The casts are the ones autodiff gives
    `sparse_xent_rows`: dz is rounded to the logits' dtype, dx to x's, and a
    block's dw to w's before the blocks are summed in it."""
    n = x.shape[0]
    block = _row_block(n) or n

    def visit(grads, xlr):
        xb, lb, rb = xlr
        zb = _head_logits(xb, w, b)
        z = zb.astype(jnp.float32)
        hit = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) == lb[:, None].astype(jnp.int32)
        lse = jax.nn.logsumexp(z, axis=-1)
        ce = lse - jnp.sum(jnp.where(hit, z, 0.0), axis=-1)
        with device_scope("grad"):
            dz = (rb[:, None] * (jnp.exp(z - lse[:, None]) - hit)).astype(zb.dtype)
            dx = ops.dot(dz, w.T).astype(xb.dtype)
            dw = grads[0] + ops.dot(xb.T, dz).astype(w.dtype)
            db = None if b is None else grads[1] + jnp.sum(dz, axis=0).astype(b.dtype)
        return (dw, db), (ce, dx)

    zero = (jnp.zeros_like(w), None if b is None else jnp.zeros_like(b))
    (dw, db), (ce, dx) = jax.lax.scan(
        visit, zero, (x.reshape(n // block, block, -1),
                      labels.reshape(n // block, block),
                      row_weights.reshape(n // block, block)))
    ce = ce.reshape(n)
    return (jnp.sum(row_weights * ce), ce), (dx.reshape(x.shape), dw, db, ce)


def _weighted_xent_bwd(res, cts):
    dx, dw, db, ce = res
    g = cts[0]                               # the sum's; `ce` carries no gradient

    def scaled(a):                           # g is a constant 1 under value_and_grad
        return None if a is None else (g * a).astype(a.dtype)

    return scaled(dx), scaled(dw), scaled(db), None, g * ce


_weighted_xent.defvjp(_weighted_xent_fwd, _weighted_xent_bwd)


def sparse_xent_weighted(x, w, b, labels, row_weights):
    """`(sum_r row_weights[r] ce[r], ce)` for the linear head x [n, f] @ w
    [f, c] (+ b) against integer labels [n]: ce [n] the per-row softmax
    cross-entropy, float32, as `sparse_xent_rows` gives it. The gradient goes
    through the SUM alone — to x, w, b and to `row_weights` (there it is ce:
    a gate that makes the weights learns through it); `ce` is for reports and
    counters and carries none.

    That is what lets a gradient cost three products of head size and not
    four: the score is the last thing a forward does and the first its
    backward undoes, and with each row's weight known a block's gradient is
    made while its logits are there (`_weighted_xent_fwd`), the backward a
    scaling by the sum's cotangent. With no gradient asked this is
    `sparse_xent_rows` and a weighted sum. Reverse mode only (a
    `jax.custom_vjp`)."""
    total, ce = _weighted_xent(x, w, b, labels, row_weights.astype(jnp.float32))
    return total, jax.lax.stop_gradient(ce)


def sparse_xent(x, w, b, labels, weights):
    """`sparse_xent_weighted` over x [b, .., f], integer labels [b, ..] and
    the weights [b, ..] each row's cross-entropy enters the score with:
    (the weighted sum, ce [b, ..]). Under a data mesh each device loops
    over the blocks of its own rows (kernel_call.per_batch_shard) — a
    sequential loop over the batch-sharded axis would make GSPMD gather x on
    every device — and gives its rows' share of the sum; shard_map's
    transpose psums w's cotangent."""
    bias = () if b is None else (b,)

    def rows(x_, l_, r_, w_, *b_):
        total, ce = sparse_xent_weighted(x_, w_, b_[0] if b_ else None, l_, r_)
        return total[None], ce

    args = (x.reshape(-1, x.shape[-1]), labels.reshape(-1), weights.reshape(-1), w) + bias
    if kernel_call.per_device_batch(x.shape[0]):
        shares, ce = kernel_call.per_batch_shard(
            rows, args, (True, True, True, False) + (False,) * len(bias))
    else:
        shares, ce = rows(*args)
    return jnp.sum(shares), ce.reshape(labels.shape)


def compute(
    loss: Union[str, Callable],
    labels: jnp.ndarray,
    preout: jnp.ndarray,
    activation_fn: Callable,
    mask: Optional[jnp.ndarray] = None,
    weights: Optional[jnp.ndarray] = None,
):
    """Return (mean_score, per_example_score).

    `per_example_score` has shape labels.shape[:-1] (feature axis summed),
    matching DL4J computeScoreArray.
    """
    name = loss if isinstance(loss, str) else getattr(loss, "__name__", "")
    if isinstance(name, str):
        name = name.lower()

    # losses always in f32 (mixed-precision policy: bf16 activations reach
    # the output layer; log-softmax/xent in bf16 is numerically unusable)
    if preout.dtype == jnp.bfloat16:
        preout = preout.astype(jnp.float32)

    if is_class_index(labels, preout.ndim):
        if name in ("mcxent", "negativeloglikelihood") and _is_softmax(activation_fn) \
                and weights is None:
            picked = jnp.take_along_axis(
                preout, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
            return reduce_score(jax.nn.logsumexp(preout, axis=-1) - picked, mask)
        labels = jax.nn.one_hot(labels, preout.shape[-1], dtype=preout.dtype)

    if name in ("mcxent", "negativeloglikelihood") and _is_softmax(activation_fn):
        # fused log-softmax cross-entropy for stability
        logp = jax.nn.log_softmax(preout, axis=-1)
        per_elem = -labels * logp
    else:
        y = activation_fn(preout)
        per_elem = get(loss)(labels, y)

    if weights is not None:
        per_elem = per_elem * weights

    per_example = jnp.sum(per_elem, axis=-1)
    return reduce_score(per_example, mask)


def _score_mask(mask, shape, dtype):
    """A label mask over scores of `shape`: a trailing singleton feature
    axis dropped ([b, t, 1] masks), broadcast, in `dtype`."""
    m = mask
    while m.ndim > len(shape) and m.shape[-1] == 1:
        m = m[..., 0]
    return jnp.broadcast_to(m, shape).astype(dtype)


def mean_weights(shape, mask: Optional[jnp.ndarray] = None):
    """(weights, m): what `reduce_score` multiplies each of `shape`'s scores
    by on the way to its mean, float32 — m / clip(sum(m), 1) under a mask
    (m the mask over `shape`), 1 / n without (m None). For a loss that takes
    the weights with the rows (`sparse_xent`)."""
    if mask is None:
        return jnp.full(shape, 1.0 / max(math.prod(shape), 1), jnp.float32), None
    m = _score_mask(mask, shape, jnp.float32)
    return m / jnp.clip(jnp.sum(m), 1.0, None), m


def reduce_score(per_example, mask: Optional[jnp.ndarray] = None):
    """Masked-mean reduction of per-example scores — the shared tail of
    `compute`, also used by fused loss paths (`ops.fused_linear_xent`) that
    produce per-example scores without a [.., features] tensor."""
    if mask is not None:
        m = _score_mask(mask, per_example.shape, per_example.dtype)
        per_example = per_example * m
        denom = jnp.clip(jnp.sum(m), 1.0, None)
        return jnp.sum(per_example) / denom, per_example

    # mean over all example-slots (batch, and time for RNN outputs)
    return jnp.mean(per_example), per_example


def _is_softmax(fn) -> bool:
    from deeplearning4j_tpu.nn import activations as _act

    return fn is _act._REGISTRY.get("softmax")
