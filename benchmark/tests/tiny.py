"""Tiny configurations and a cell context for the CPU rehearsals: the same
files, functions and control flow as a chip run, at sizes a test can hold."""
from __future__ import annotations

import copy
import json
import os
import types

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def gpt2(precision="float32") -> dict:
    cfg = copy.deepcopy(config("gpt2-small"))
    cfg.update(n_embd=32, n_head=4, n_layer=2, vocab_size=64, n_positions=16)
    cfg["program"]["args"] = {"num_classes": 64, "max_length": 16,
                              "d_model": 32, "n_heads": 4, "n_layers": 2}
    cfg["program"]["precision"] = precision
    cfg["input"] = {"kind": "tokens", "seq_len": 16, "vocab": 64}
    return cfg


def resnet50(precision="float32") -> dict:
    cfg = copy.deepcopy(config("resnet50"))
    cfg.update(num_classes=10, input_shape=[64, 64, 3])
    cfg["program"]["args"] = {"num_classes": 10, "input_shape": [64, 64, 3]}
    cfg["program"]["precision"] = precision
    cfg["input"] = {"kind": "images", "shape": [64, 64, 3], "classes": 10}
    return cfg


def ctx(cell: dict, seed=7, seconds=1.0):
    """What run.py's `context` builds, without the profiler."""
    from benchmark import harness

    class NoCapture:
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    import time

    return types.SimpleNamespace(
        cell=cell, cfg=cell["cfg"], traffic=cell["traffic_params"], seed=seed,
        seconds=seconds, trace=False, setup=harness.Setup(time.perf_counter()),
        compiles=harness.CompileCounter(), capture=NoCapture(), capture_host=NoCapture(),
        place_rows=lambda a: a, replicate=lambda t: t)
