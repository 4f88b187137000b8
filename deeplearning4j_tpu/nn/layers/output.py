"""Output / loss-bearing layers: OutputLayer, RnnOutputLayer, LossLayer,
CenterLossOutputLayer, and `LoopExitOutput` (the exit-weighted loss over the
passes of a `LoopedStack`; no DL4J counterpart).

Reference: nn/conf/layers/{OutputLayer,RnnOutputLayer,LossLayer}.java,
nn/conf/layers/CenterLossOutputLayer.java; runtime BaseOutputLayer
computeScore (MultiLayerNetwork.java:2244 calls
outputLayer.computeScore(l1, l2)).

An output layer is a Dense layer plus a loss contract:
    loss(params, x, labels, mask) -> (scalar, per_example)
The network's training objective = output.loss + l1/l2 terms.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn import losses as loss_mod
from deeplearning4j_tpu.nn import initializers as init_mod
from deeplearning4j_tpu.nn.layers.base import Layer, column_parallel_specs, register_layer
from deeplearning4j_tpu.nn.layers.dense import Dense, _flatten_if_needed
from deeplearning4j_tpu.ops import fused_linear_xent
from deeplearning4j_tpu.ops import linear as ops
from deeplearning4j_tpu.telemetry.trace import device_scope

F32 = jnp.float32


class BaseOutputLayer(Layer):
    """Mixin contract for layers that terminate a network with a loss."""

    def compute_loss(self, params, x, labels, *, state, mask=None, rng=None):
        """Return (mean_score, per_example_scores, new_state)."""
        raise NotImplementedError


@register_layer
@dataclass
class Output(Dense, BaseOutputLayer):
    """Dense + loss (DL4J OutputLayer). Default act=softmax, loss=MCXENT."""

    loss: Optional[str] = None  # loss function name

    def _loss_name(self):
        return self.loss or "mcxent"

    def _act(self):
        return self.act_fn("softmax")

    def preout(self, params, x):
        x = _flatten_if_needed(x)
        z = ops.dot(x, params["W"])
        if self.has_bias:
            z = ops.bias_add(z, params["b"])
        return z

    def apply(self, params, x, *, state, train, rng, mask=None):
        return self._act()(self.preout(params, x)), state

    def _softmax_xent(self) -> bool:
        return (self._loss_name() in ("mcxent", "negativeloglikelihood")
                and loss_mod._is_softmax(self._act()))

    def _bias(self, params):
        return params["b"] if self.has_bias and "b" in params else None

    def _fused_xent_per_example(self, params, x, labels):
        """Per-example scores of mcxent on softmax against dense labels
        WITHOUT materializing the [.., n_out] logits in HBM, where
        `ops.fused_linear_xent` admits the shape (wide vocab, tileable);
        None -> the builtin XLA path."""
        if not self._softmax_xent():
            return None
        return fused_linear_xent(_flatten_if_needed(x), params.get("W"),
                                 self._bias(params), labels)

    def _index_xent(self, params, x, labels, mask):
        """Integer class labels ([b] or [b, t]) on a softmax head with
        mcxent: (score, per_example) from the head and the loss in row
        blocks (`losses.sparse_xent`), so no [.., n_out] label array exists.
        The rows go in with the weights `reduce_score` would give them, so
        under a gradient a block's share of it is made while its logits are
        there. None for dense labels or another loss (-> the paths below;
        an integer label is then expanded by `losses.compute`)."""
        if (not jnp.issubdtype(jnp.result_type(labels), jnp.integer)
                or not self._softmax_xent()):
            return None
        x2 = x if jnp.ndim(x) == jnp.ndim(labels) + 1 else _flatten_if_needed(x)
        if x2.shape[:-1] != labels.shape:
            return None
        weights, m = loss_mod.mean_weights(labels.shape, mask)
        score, ce = loss_mod.sparse_xent(x2, params["W"], self._bias(params), labels, weights)
        return score, ce if m is None else ce * m

    def compute_loss(self, params, x, labels, *, state, mask=None, rng=None):
        scored = self._index_xent(params, x, labels, mask)
        if scored is not None:
            return (*scored, state)
        per_example = self._fused_xent_per_example(params, x, labels)
        if per_example is not None:
            score, per_ex = loss_mod.reduce_score(per_example, mask)
            return score, per_ex, state
        z = self.preout(params, x)
        score, per_ex = loss_mod.compute(
            self._loss_name(), labels, z, self._act(), mask=mask
        )
        return score, per_ex, state


@register_layer
@dataclass
class RnnOutput(Output):
    """Per-timestep output over [b, t, f] input (DL4J RnnOutputLayer).

    Loss averages over batch*time with mask support
    (nn/layers/recurrent/RnnOutputLayer.java)."""

    def output_type(self, input_type):
        t = input_type.timesteps if isinstance(input_type, it.Recurrent) else -1
        return it.Recurrent(self.n_out, t)

    def preout(self, params, x):
        z = ops.dot(x, params["W"])  # [b,t,f]@[f,n] -> [b,t,n]
        if self.has_bias:
            z = ops.bias_add(z, params["b"])
        return z


def exit_pdf(lam):
    """The exit gate's lam [.., steps, t] in (0, 1) -> the distribution over
    the passes a token: p_s = lam_s prod_{j<s}(1 - lam_j), the last pass
    taking what is left, p_steps = prod_{j<steps}(1 - lam_j). Sums to one."""
    stay = jnp.cumprod(1.0 - lam[..., :-1, :], axis=-2)          # still running after pass s
    reach = jnp.concatenate([jnp.ones_like(lam[..., :1, :]), stay], axis=-2)
    leave = jnp.concatenate([lam[..., :-1, :], jnp.ones_like(lam[..., :1, :])], axis=-2)
    return reach * leave


def entropy(p, axis):
    """- sum p log p over `axis`, with p log p = 0 at p = 0 in value and in
    gradient (a gate that saturated gives an exact zero)."""
    some = p > 0
    return -jnp.sum(jnp.where(some, p * jnp.log(jnp.where(some, p, 1.0)), 0.0), axis=axis)


@register_layer
@dataclass
class LoopExitOutput(RnnOutput):
    """The head and loss of a looped stack: x [b, steps, t, f]
    (`LoopedStack`'s output), integer labels [b, t]. ONE head matrix W reads
    the state of every pass, a learned gate lam_s = sigmoid(h_s . w + b)
    (shared by the passes) makes the exit distribution p (`exit_pdf`), and a
    token's score is the loss expected under it less an entropy bonus:

        sum_s p_s l_s - beta H(p),   l_s the cross-entropy of pass s

    so the gate learns through both terms and the stack through every l_s
    and through lam. The rows of all passes go through `losses.sparse_xent`
    in one call (no [.., n_out] array), each with the weight its l_s enters
    the score with, p_s mask / sum(mask): under a gradient a block's share of
    it is made while its logits are there (nothing is recomputed), and the
    gate's gradient through the expectation is the weights' own, l_s. The
    gate, the distribution and the weights are float32.
    `output()` is the LAST pass's softmax: no pass is skipped at inference.
    Params `W` (`b` with `has_bias`) and `gate` {w [f], b []}. State
    `counters` (running sums a step, `telemetry.fit_log()` key `exit`): the
    mean distribution `exit_p` [steps], its mean entropy `exit_entropy`, the
    mean cross-entropy a pass `loss_by_pass` [steps]."""

    beta: float = 0.1

    sp_safe = False

    def _passes(self, input_type):
        if not isinstance(input_type, it.RecurrentPasses):
            raise ValueError(f"LoopExitOutput reads a looped stack's passes "
                             f"[b, steps, t, f], not {input_type}")
        return input_type.passes

    def output_type(self, input_type):
        self._passes(input_type)
        return it.Recurrent(self.n_out, input_type.timesteps)

    def init_params(self, rng, input_type):
        self._passes(input_type)
        k_head, k_gate = jax.random.split(rng)
        p = super().init_params(k_head, input_type)
        n_in = self.resolve_n_in(input_type)
        p["gate"] = {"w": init_mod.init(self.weight_init or "xavier", k_gate, (n_in, 1))[:, 0],
                     "b": jnp.zeros((), F32)}
        return p

    def init_state(self, input_type):
        steps = self._passes(input_type)
        return {"counters": {"steps": jnp.zeros((), jnp.int32),
                             "exit_p": jnp.zeros((steps,), F32),
                             "exit_entropy": jnp.zeros((), F32),
                             "loss_by_pass": jnp.zeros((steps,), F32)}}

    def counter_summary(self, added):
        """Per-step means of the counters over a fit, under `exit`."""
        steps = max(int(added["steps"][0]), 1)
        p = (added["exit_p"] / steps).tolist()
        return "exit", {
            "steps": int(added["steps"][0]),
            "exit_p": p,
            "expected_passes": sum((s + 1) * v for s, v in enumerate(p)),
            "exit_entropy": float(added["exit_entropy"][0]) / steps,
            "loss_by_pass": (added["loss_by_pass"] / steps).tolist(),
        }

    def regularizable(self, params):
        return {"W": params["W"], "gate/w": params["gate"]["w"]}

    def tensor_partition_specs(self, params, model_axis="model", model_size=1):
        from jax.sharding import PartitionSpec as P

        head = {k: v for k, v in params.items() if k != "gate"}
        return {**column_parallel_specs(head, model_axis, model_size),
                "gate": {"w": P(), "b": P()}}

    def apply(self, params, x, *, state, train, rng, mask=None):
        return super().apply(params, x[:, -1], state=state, train=train, rng=rng, mask=mask)

    def compute_loss(self, params, x, labels, *, state, mask=None, rng=None):
        if (not jnp.issubdtype(jnp.result_type(labels), jnp.integer)
                or not self._softmax_xent()
                or jnp.ndim(x) != 4 or labels.shape != (x.shape[0], x.shape[2])):
            raise TypeError(
                f"LoopExitOutput: passes [b, steps, t, f] against integer labels [b, t] "
                f"under softmax + mcxent, not x {jnp.shape(x)} against "
                f"{jnp.result_type(labels)} labels {jnp.shape(labels)} under "
                f"{self.activation or 'softmax'} + {self._loss_name()}")
        with device_scope("exit"):
            gate = params["gate"]
            lam = jax.nn.sigmoid(jnp.einsum("bstf,f->bst", x.astype(F32), gate["w"],
                                            precision=jax.lax.Precision.HIGHEST) + gate["b"])
            p = exit_pdf(lam)
            h = entropy(p, axis=1)
            weights, m = loss_mod.mean_weights(labels.shape, mask)
        # the gate learns through the weights: d(expected)/d(p weights) = by_pass
        expected, by_pass = loss_mod.sparse_xent(
            x, params["W"], self._bias(params),
            jnp.broadcast_to(labels[:, None, :], x.shape[:3]),
            p * weights[:, None, :])                                 # by_pass [b, steps, t] float32
        with device_scope("exit"):
            score = expected - self.beta * jnp.sum(h * weights)
            per_ex = jnp.sum(p * by_pass, axis=1) - self.beta * h
            if m is not None:
                per_ex = per_ex * m
        with device_scope("counters"):
            def mean(a):  # over the tokens the score counts: [b, t] -> [], [b, steps, t] -> [steps]
                return jnp.einsum("b...t,bt->...", jax.lax.stop_gradient(a), weights)

            c = state["counters"]
            state = {"counters": {
                "steps": c["steps"] + 1, "exit_p": c["exit_p"] + mean(p),
                "exit_entropy": c["exit_entropy"] + mean(h),
                "loss_by_pass": c["loss_by_pass"] + mean(by_pass)}}
        return score, per_ex, state


@register_layer
@dataclass
class LossLayer(BaseOutputLayer, Layer):
    """Loss without params: applies activation + loss to its input directly
    (nn/conf/layers/LossLayer.java)."""

    loss: Optional[str] = None

    sp_safe = True  # per-slot loss; the SP wrapper reweights the mean

    def output_type(self, input_type):
        return input_type

    def has_params(self):
        return False

    def apply(self, params, x, *, state, train, rng, mask=None):
        return self.act_fn("identity")(x), state

    def compute_loss(self, params, x, labels, *, state, mask=None, rng=None):
        score, per_ex = loss_mod.compute(
            self.loss or "mcxent", labels, x, self.act_fn("identity"), mask=mask
        )
        return score, per_ex, state


@register_layer
@dataclass
class CenterLossOutput(Output):
    """Output layer with center loss auxiliary term
    (nn/conf/layers/CenterLossOutputLayer.java, runtime
    nn/layers/training/CenterLossOutputLayer.java).

    total = primary_loss + lambda * mean ||x - c_{y}||^2 ; centers updated by
    EMA with rate alpha. Centers are STATE (not gradient-trained), matching
    the reference's in-updater center update trick.
    """

    alpha: float = 0.05
    lambda_: float = 2e-4

    # the EMA center update scatters over the LOCAL shard's examples only —
    # sequence sharding would silently compute per-shard centers
    sp_safe = False

    def init_state(self, input_type):
        n_in = self.resolve_n_in(input_type)
        return {"centers": jnp.zeros((self.n_out, n_in), jnp.float32)}

    def compute_loss(self, params, x, labels, *, state, mask=None, rng=None):
        x2 = _flatten_if_needed(x)
        z = self.preout(params, x2)
        score, per_ex = loss_mod.compute(
            self._loss_name(), labels, z, self._act(), mask=mask
        )
        centers = state["centers"]
        cls = jnp.argmax(labels, axis=-1)
        c = jnp.take(centers, cls, axis=0)  # [b, n_in]
        diff = x2 - c
        center_l = 0.5 * jnp.mean(jnp.sum(diff * diff, axis=-1))
        # EMA center update (scatter-mean per class), outside the gradient
        upd = jax.lax.stop_gradient(diff)
        num = jnp.zeros_like(centers).at[cls].add(upd)
        cnt = jnp.zeros((centers.shape[0],), jnp.float32).at[cls].add(1.0)
        new_centers = centers + self.alpha * num / jnp.clip(cnt, 1.0, None)[:, None]
        new_state = {"centers": new_centers}
        return score + self.lambda_ * center_l, per_ex, new_state
