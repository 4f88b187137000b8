"""FrozenLayer wrapper (nn/conf/layers/misc/FrozenLayer.java, runtime
nn/layers/FrozenLayer.java): delegates forward to the wrapped layer; its
params receive no updates (gradient zeroed in the train step via the
`frozen` marker, the functional analogue of the reference's no-op updater).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Union

from deeplearning4j_tpu.nn.layers.base import Layer, nested_layer, register_layer


@register_layer
@dataclass
class Frozen(Layer):
    underlying: Optional[Union[dict, Layer]] = None

    def __post_init__(self):
        self._inner = nested_layer(self.underlying)

    @property
    def inner(self) -> Layer:
        return self._inner

    frozen = True

    def output_type(self, input_type):
        return self._inner.output_type(input_type)

    def init_params(self, rng, input_type):
        return self._inner.init_params(rng, input_type)

    def init_state(self, input_type):
        return self._inner.init_state(input_type)

    def has_params(self):
        return self._inner.has_params()

    def regularizable(self, params):
        return {}

    def apply(self, params, x, *, state, train, rng, mask=None):
        # train=False for the wrapped layer: BN uses running stats, no dropout
        return self._inner.apply(params, x, state=state, train=False, rng=rng,
                                 mask=mask)

    def propagate_mask(self, mask, input_type):
        return self._inner.propagate_mask(mask, input_type)

    def to_json(self):
        d = {"type": "Frozen"}
        if self._inner is not None:
            d["underlying"] = self._inner.to_json()
        return d
