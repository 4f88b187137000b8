"""What the plain references share: seeded keys, matmul/conv at "highest"
precision with an optional rounding of the operands to a lower precision
(the control), the two optimizers as DL4J defines them, the three reference
training steps, and the comparison that decides `correct` for a training
cell. Imports nothing of `deeplearning4j_tpu`.
"""
from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def seed_key(seed: int, stream: int = 0):
    """A key from any non-negative whole number (seeds pass 2**31)."""
    key = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, int(seed) >> 31)
    return jax.random.fold_in(key, stream)


# ---------------------------------------------------------------------------
# lower-precision operands (the control of "How correct is decided", step 2)
# ---------------------------------------------------------------------------
_FP8_MAX = 448.0   # float8_e4m3fn


def round_operand(a, operand):
    """`a` rounded to `operand` and back to float32, with the gradient
    passed straight through the rounding (so the control still trains).
    bfloat16 rounds in place; float8_e4m3fn and int8 are scaled per tensor
    by the largest magnitude, as fp8/int8 recipes scale them."""
    if operand is None:
        return a
    if operand == "bfloat16":
        q = a.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        amax = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
        if operand == "float8_e4m3fn":
            s = amax / _FP8_MAX
            q = (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        elif operand == "int8":
            s = amax / 127.0
            q = jnp.round(a / s) * s
        else:
            raise ValueError(f"unknown operand precision {operand!r}")
    return a + lax.stop_gradient(q - a)


def _policy(operand):
    """"float8_e4m3fn" rounds the operands of every product; with the
    suffix "_act" every activation the model stores is rounded too (the
    products' results here, BN outputs and residual sums through `stored`),
    as a mixed-precision policy does: the program's stores them in bf16."""
    if operand and operand.endswith("_act"):
        return operand[:-4], operand[:-4]
    return operand, None


def stored(operand=None):
    """Rounding of an activation the model stores (a BN output, a residual
    sum): identity unless the policy is "<precision>_act"."""
    _, act = _policy(operand)
    return lambda a: round_operand(a, act)


def matmul(operand=None):
    op, act = _policy(operand)

    def mm(a, b):
        out = jnp.matmul(round_operand(a, op), round_operand(b, op),
                         precision=HIGHEST)
        return round_operand(out, act)
    return mm


def conv(operand=None):
    op, act = _policy(operand)

    def cv(x, w, stride, padding):
        out = lax.conv_general_dilated(
            round_operand(x, op), round_operand(w, op),
            window_strides=stride, padding=padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
        return round_operand(out, act)
    return cv


# ---------------------------------------------------------------------------
# optimizers, from DL4J's updater definitions
# ---------------------------------------------------------------------------
class Adam:
    """AdamUpdater: bias correction folded into the step size, epsilon
    added to sqrt(v) (not under the root)."""

    slot = "m"   # the state leaf the first gradient is read from

    def __init__(self, learning_rate, beta1=0.9, beta2=0.999, epsilon=1e-8):
        self.lr, self.b1, self.b2, self.eps = learning_rate, beta1, beta2, epsilon

    def init(self, params):
        z = {k: jnp.zeros_like(v) for k, v in params.items()}
        return {"m": z, "v": dict(z), "t": 0}

    def apply(self, params, grads, st):
        t = st["t"] + 1
        m = {k: self.b1 * st["m"][k] + (1 - self.b1) * grads[k] for k in params}
        v = {k: self.b2 * st["v"][k] + (1 - self.b2) * grads[k] ** 2
             for k in params}
        alpha = self.lr * np.sqrt(1 - self.b2 ** t) / (1 - self.b1 ** t)
        new = {k: params[k] - alpha * m[k] / (jnp.sqrt(v[k]) + self.eps)
               for k in params}
        return new, {"m": m, "v": v, "t": t}

    def first_gradient_scale(self) -> float:
        """g = scale * m after the first step (m = (1 - b1) g)."""
        return 1.0 / (1.0 - self.b1)


class Nesterovs:
    """NesterovsUpdater: v' = mu v - lr g; theta += mu v' - lr g."""

    slot = "v"

    def __init__(self, learning_rate, momentum=0.9):
        self.lr, self.mu = learning_rate, momentum

    def init(self, params):
        return {"v": {k: jnp.zeros_like(v) for k, v in params.items()}}

    def apply(self, params, grads, st):
        v = {k: self.mu * st["v"][k] - self.lr * grads[k] for k in params}
        new = {k: params[k] + self.mu * v[k] - self.lr * grads[k]
               for k in params}
        return new, {"v": v}

    def first_gradient_scale(self) -> float:
        """g = scale * v after the first step (v = -lr g)."""
        return -1.0 / self.lr


# ---------------------------------------------------------------------------
# the reference's three training steps
# ---------------------------------------------------------------------------
def _norms(tree):
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in tree.items()}


def make_grad_fn(mod, cfg, operand=None):
    """(params, state, x, labels) -> (mean loss incl. penalty, grads,
    new state) over the whole batch. Independent rows are differentiated
    `mod.ROWS_PER_BLOCK` at a time and added, so the float32 reference of a
    full timed batch fits beside nothing else; a model whose rows are
    coupled (batch statistics) is differentiated whole."""

    def block(params, state, x, y):
        def f(p):
            with jax.default_matmul_precision("highest"):
                return mod.loss_sum(p, state, x, y, cfg, operand)
        (s, new_state), g = jax.value_and_grad(f, has_aux=True)(params)
        return s, g, new_state

    def pen(params):
        return jax.value_and_grad(lambda p: mod.penalty(p, cfg))(params)

    block = jax.jit(block)
    pen = jax.jit(pen)
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))

    def grad_fn(params, state, x, y):
        n = x.shape[0]
        rows = n if mod.COUPLED_ROWS else mod.ROWS_PER_BLOCK
        total, grads = 0.0, None
        for lo in range(0, n, rows):
            s, g, state_out = block(params, state, x[lo:lo + rows],
                                    y[lo:lo + rows])
            total = total + s
            grads = g if grads is None else add(grads, g)
        count = mod.loss_count(x)
        loss = total / count
        grads = {k: v / count for k, v in grads.items()}
        if mod.penalty is not None:
            p, pg = pen(params)
            loss = loss + p
            grads = {k: grads[k] + pg[k] for k in grads}
        return loss, grads, state_out

    return grad_fn


def train_steps(mod, cfg, params0, state0, batches, operand=None):
    """Follow the program's first len(batches) steps from the same seeded
    weights. Returns the numbers the comparison needs and nothing large:
    each step's loss, the norm of each leaf's first gradient, and the norm
    of each leaf's change after the last step."""
    grad_fn = make_grad_fn(mod, cfg, operand)
    opt = mod.optimizer(cfg)
    params, state, st = params0, state0, opt.init(params0)
    losses, grad_norms = [], None
    for i, (x, y) in enumerate(batches):
        loss, grads, state = grad_fn(params, state, x, y)
        losses.append(float(loss))
        if i == 0:
            grad_norms = _norms(grads)
        params, st = opt.apply(params, grads, st)
        del grads
    delta = _norms({k: params[k] - params0[k] for k in params0})
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta}


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------
def leaf_gaps(got: dict, ref: dict) -> dict:
    """Per leaf, |got - ref| measured against the reference's norm of that
    leaf or of the median leaf, whichever is larger (some gradients are all
    but zero)."""
    floor = statistics.median(ref.values())
    return {k: abs(got[k] - r) / max(r, floor, 1e-30) for k, r in ref.items()}


def leaf_statistic(got: dict, ref: dict, statistic: str, suffix=None):
    """("worst" | "median") of the leaf gaps, over the leaves whose name
    ends in `suffix` (all when None). Returns (gap, leaf or note)."""
    gaps = {k: v for k, v in leaf_gaps(got, ref).items()
            if suffix is None or k.endswith(suffix)}
    if not all(np.isfinite(v) for v in gaps.values()):
        return float("inf"), "a leaf is not finite"
    if statistic == "worst":
        leaf = max(gaps, key=gaps.get)
        return gaps[leaf], f"worst leaf {leaf}"
    if statistic == "median":
        return statistics.median(gaps.values()), f"median of {len(gaps)} leaves"
    raise ValueError(statistic)


# name, which norms, statistic over the leaves, leaves by suffix
WORST_LEAF = (("grad_norm_gap", "grad_norms", "worst", None),
              ("delta_norm_gap", "delta_norms", "worst", None))


def compare_training(got: dict, ref: dict, limits: dict, comparisons=WORST_LEAF):
    """`got` and `ref` as `train_steps` returns them. Returns a list of
    (name, value, limit, ok, note) rows, one for every number compared."""
    rows = []
    for i, (a, b) in enumerate(zip(got["losses"], ref["losses"])):
        gap = abs(a - b) / abs(b) if np.isfinite(a) else float("inf")
        rows.append((f"loss_gap_step{i + 1}", gap, limits["loss_gap"],
                     gap <= limits["loss_gap"], f"program {a!r} reference {b!r}"))
    for name, key, statistic, suffix in comparisons:
        gap, note = leaf_statistic(got[key], ref[key], statistic, suffix)
        rows.append((name, gap, limits[name], gap <= limits[name], note))
    return rows
