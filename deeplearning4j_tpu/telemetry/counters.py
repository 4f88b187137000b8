"""Counters that come out of the compiled step.

A layer that counts on the device (``RoutedExperts``: assignments per expert
held, dropped assignments, buffer rows offered) keeps the running totals in
its STATE, under the key ``counters`` — they ride the train step like a
BatchNorm's running statistics, so a step costs no host sync. A fit reads
them ONCE, after its last step (whose score it has already waited for), and
reports what the fit added in its ``telemetry.fit_log()`` entry, under the
key and in the form the layer's ``counter_summary`` gives (``experts`` for
``RoutedExperts``; a layer without one of its own: the sums, under
``counters``). Integer totals are int32 and wrap; a fit's difference is taken
modulo 2**32 and is exact below that.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np


def _device_counters(model) -> Dict[str, Any]:
    state = getattr(model, "state", None)
    if not isinstance(state, dict):
        return {}
    return {k: v["counters"] for k, v in state.items()
            if isinstance(v, dict) and "counters" in v}


def begin(model) -> Optional[Dict[str, Any]]:
    """The totals this fit starts from, or None for a model that counts
    nothing. Read from the device once a model (what it was built or loaded
    with); afterwards the previous fit's reading is the start."""
    import jax

    dev = _device_counters(model)
    if not dev:
        return None
    base = getattr(model, "_counter_base", None)
    if base is None or set(base) != set(dev):
        base = jax.device_get(dev)
        model._counter_base = base
    return base


def _since(before, after):
    out = {}
    for name, now in after.items():
        now, was = np.atleast_1d(now), np.atleast_1d(before[name])
        if np.issubdtype(now.dtype, np.integer):
            out[name] = np.subtract(now.astype(np.uint32), was.astype(np.uint32),
                                    dtype=np.uint32).astype(np.int64)
        else:
            out[name] = now.astype(np.float64) - was
    return out


def _layer(model, key: str):
    """The layer whose state is `model.state[key]`."""
    vertices = getattr(getattr(model, "conf", None), "vertices", None)
    if vertices is not None:        # a graph's state is keyed by vertex
        return vertices[key].layer
    return model.layers[int(key.rsplit("_", 1)[1])]


def end(model, base) -> Dict[str, List[Dict[str, Any]]]:
    """What the fit added: `fit_log()` key -> one entry per counting layer
    in network order, each as its layer summarises it
    (`Layer.counter_summary`). The fit's one device read. Empty when
    `begin` gave None or the state is gone (a fit that died mid-step)."""
    import jax

    if base is None:
        return {}
    try:
        now = jax.device_get(_device_counters(model))
    except Exception:  # noqa: BLE001 — a dying fit's donated state
        return {}
    if set(now) != set(base):
        return {}
    model._counter_base = now

    def order(k):
        tail = k.rsplit("_", 1)[-1]
        return (0, int(tail)) if tail.isdigit() else (1, k)

    out: Dict[str, List[Dict[str, Any]]] = {}
    for k in sorted(now, key=order):
        name, entry = _layer(model, k).counter_summary(_since(base[k], now[k]))
        out.setdefault(name, []).append({"layer": k, **entry})
    return out
