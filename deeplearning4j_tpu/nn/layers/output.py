"""Output / loss-bearing layers: OutputLayer, RnnOutputLayer, LossLayer,
CenterLossOutputLayer.

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
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.nn.layers.dense import Dense, _flatten_if_needed
from deeplearning4j_tpu.ops import fused_linear_xent
from deeplearning4j_tpu.ops import linear as ops


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

    def _index_xent_per_example(self, params, x, labels):
        """Integer class labels ([b] or [b, t]) on a softmax head with
        mcxent: the head and the loss in row blocks
        (`losses.sparse_xent`), so no [.., n_out] label array exists.
        None for dense labels or another loss (-> the paths below; an
        integer label is then expanded by `losses.compute`)."""
        if (not jnp.issubdtype(jnp.result_type(labels), jnp.integer)
                or not self._softmax_xent()):
            return None
        x2 = x if jnp.ndim(x) == jnp.ndim(labels) + 1 else _flatten_if_needed(x)
        if x2.shape[:-1] != labels.shape:
            return None
        return loss_mod.sparse_xent(x2, params["W"], self._bias(params), labels)

    def compute_loss(self, params, x, labels, *, state, mask=None, rng=None):
        per_example = self._index_xent_per_example(params, x, labels)
        if per_example is None:
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
