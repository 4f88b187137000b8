"""Plain reference for the `resnet50` configuration.

ResNet-50 v1 (He, Zhang, Ren, Sun 2015, "Deep Residual Learning for Image
Recognition", table 1) as Deeplearning4j's zoo builds it: a 7x7/2 stem
convolution, 3x3/2 max pooling, four stages of (3, 4, 6, 3) bottleneck
blocks (1x1 reduce carrying the stage's stride, 3x3, 1x1 expand, no conv
bias), batch normalisation after every convolution, a projection shortcut
(1x1 convolution + BN) on the first block of each stage, ReLU after the
addition, global average pooling and a dense softmax layer. All paddings are
"SAME" as TensorFlow defines it. Written in float32 `jax.numpy` at
precision "highest"; imports nothing of `deeplearning4j_tpu`.

Training semantics, from DL4J's definitions:
  * BatchNormalization normalises with the batch's mean and biased variance
    over (N, H, W), eps 1e-5, and tracks running = 0.9 running + 0.1 batch;
    inference normalises with the running statistics.
  * loss = mean over the batch of -log softmax(logits)[label]
           + 0.5 * l2 * sum of squares of every convolution and dense
             weight (not BN gains, offsets or the dense bias), l2 = 1e-4.
  * NesterovsUpdater: v' = mu v - lr g; theta += mu v' - lr g, mu = 0.9.

The batch statistics couple the rows of a batch, so the reference cannot
add blocks of rows; it differentiates the whole batch with each bottleneck
block rematerialised (`jax.checkpoint`), which holds a float32 batch of 128
in a few GB.

Parameters are a flat dict: "<unit>.w" convolution kernels HWIO, "<unit>.g"
/ ".b" BN gain and offset, "fc.w" / "fc.b"; state "<unit>.mean" / ".var".
A unit is "stem" or "s<stage>.<block>.<a|b|c|sc>".
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import common

# ---------------------------------------------------------------------------
# Limits, each from two readings on the chip at the cell's own size
# (benchmark/tests/read_limits.py, PR 23).
#
# Serving (`resnet50_serve_mix`: proven, then left out of BENCHMARK.json for
# its size, PERF.md section 7), 12 seeds of the program, the control on 4:
#   answer_gap   sound 0.0232 .. 0.0317 (steady); the float8_e4m3fn control
#                0.142 .. 0.197 (operands), 0.167 .. 0.330 (with stored
#                activations): 4.5 x. Limit between, 2.2 x above the sound
#                runs' largest and 2.0 x below the control's smallest.
#
# Training (no cell uses these yet: PERF.md section 7). At 128 images, 12
# seeds: loss_gap sound 3.1e-5 .. 1.7e-4, control 1.7e-4 .. 9.7e-4;
# grad_norm_gap (median leaf) sound 1.95e-3 .. 2.71e-3, control
# 4.6e-3 .. 5.8e-3 whichever way float8 is applied; kernel_grad_norm_gap
# (worst kernel) sound 5.3e-3 .. 1.29e-2, control 1.5e-2 .. 3.5e-2;
# delta_norm_gap (median leaf) sound 2.0e-3 .. 2.9e-3, control
# 4.7e-3 .. 6.1e-3. The control's smallest is 1.8 x the sound runs' largest
# at best, under the 3 x a limit needs: these values are where a limit WOULD
# sit and are recorded, not proven.
# ---------------------------------------------------------------------------
LIMITS = {"loss_gap": 5.0e-4, "grad_norm_gap": 3.7e-3, "kernel_grad_norm_gap": 0.04,
          "delta_norm_gap": 9.0e-3, "answer_gap": 0.07}
COMPARISONS = (("grad_norm_gap", "grad_norms", "median", None),
               ("kernel_grad_norm_gap", "grad_norms", "worst", ".w"),
               ("delta_norm_gap", "delta_norms", "median", None))
CONTROL = "float8_e4m3fn"            # the precision below mixed bf16

STAGES = ((2, (64, 64, 256), 3, 1), (3, (128, 128, 512), 4, 2),
          (4, (256, 256, 1024), 6, 2), (5, (512, 512, 2048), 3, 2))
BN_EPS = 1e-5
BN_DECAY = 0.9


def units(cfg: dict):
    """(unit, kernel, c_in, c_out, stride) for every conv+BN unit."""
    out = [("stem", 7, cfg["input_shape"][2], 64, 2)]
    c_in = 64
    for stage, (f1, f2, f3), blocks, stride in STAGES:
        for b in range(blocks):
            s = stride if b == 0 else 1
            u = f"s{stage}.{b}."
            out += [(u + "a", 1, c_in, f1, s), (u + "b", 3, f1, f2, 1),
                    (u + "c", 1, f2, f3, 1)]
            if b == 0:
                out.append((u + "sc", 1, c_in, f3, s))
            c_in = f3
    return out


def leaf_shapes(cfg: dict) -> dict:
    shapes = {}
    for u, k, ci, co, _ in units(cfg):
        shapes[u + ".w"] = (k, k, ci, co)
        shapes[u + ".g"] = (co,)
        shapes[u + ".b"] = (co,)
    shapes["fc.w"] = (2048, cfg["num_classes"])
    shapes["fc.b"] = (cfg["num_classes"],)
    return shapes


def state_shapes(cfg: dict) -> dict:
    shapes = {}
    for u, _, _, co, _ in units(cfg):
        shapes[u + ".mean"] = (co,)
        shapes[u + ".var"] = (co,)
    return shapes


def _layer(unit: str) -> str:
    """The zoo graph's name for a unit: s2.0.a -> s2_0_a."""
    return unit.replace(".", "_")


def program_paths(cfg: dict) -> dict:
    paths = {}
    for name in leaf_shapes(cfg):
        unit, leaf = name.rsplit(".", 1)
        if unit == "fc":
            paths[name] = ("out", {"w": "W", "b": "b"}[leaf])
        elif leaf == "w":
            paths[name] = (_layer(unit) + "_conv", "W")
        else:
            paths[name] = (_layer(unit) + "_bn",
                           {"g": "gamma", "b": "beta"}[leaf])
    return paths


def program_state_paths(cfg: dict) -> dict:
    return {name: (_layer(name.rsplit(".", 1)[0]) + "_bn",
                   name.rsplit(".", 1)[1])
            for name in state_shapes(cfg)}


LAST_BN_GAIN = 0.2


def init_params(cfg: dict, seed: int) -> dict:
    """Seeded weights in one jitted call: kernels and the dense matrix
    N(0, 2 / fan_in) (He et al. 2015b, as the zoo's "relu" init), BN gains
    1 + N(0, 0.1), offsets and the dense bias N(0, 0.1) — except the gain of
    the last BN of every residual branch, which is 0.2 (1 + N(0, 0.1)).

    That is Goyal et al. 2017's residual initialisation (they use 0; 0.2
    keeps a gradient in every leaf). It is there for the yardstick's sake:
    with all gains near 1 a 50-layer BN network at random weights is so
    ill-conditioned that the bf16 program, a float8 control and a float32
    reference differed from one another by the same 20-40 % in the worst
    leaf's gradient norm (my chip runs, PR 23), and no limit could tell a
    lower precision from a sound run. Branches that start small make the
    first steps well-conditioned. The shapes, and so the timings, are the
    same."""
    shapes = leaf_shapes(cfg)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            n = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if name.endswith(".w"):
                fan_in = math.prod(shape[:-1])
                out[name] = n * math.sqrt(2.0 / fan_in)
            elif name.endswith(".c.g"):
                out[name] = LAST_BN_GAIN * (1.0 + 0.1 * n)
            elif name.endswith(".g"):
                out[name] = 1.0 + 0.1 * n
            else:
                out[name] = 0.1 * n
        return out

    return jax.jit(make)(common.seed_key(seed))


def init_state(cfg: dict, seed: int) -> dict:
    """A fresh model's running statistics: mean 0, variance 1."""
    return {k: (jnp.ones if k.endswith(".var") else jnp.zeros)(s, jnp.float32)
            for k, s in state_shapes(cfg).items()}


def _forward(params, state, x, cfg, train, operand=None):
    """Images [n, h, w, c] -> (logits [n, classes], new running stats)."""
    cv = common.conv(operand)
    mm = common.matmul(operand)
    stored = common.stored(operand)   # identity unless the policy is "_act"
    new_state = {}

    def unit(x, u, stride, relu):
        x = cv(x, params[u + ".w"], (stride, stride), "SAME")
        if train:
            mean = x.mean((0, 1, 2))
            var = ((x - mean) ** 2).mean((0, 1, 2))
            new_state[u + ".mean"] = (BN_DECAY * state[u + ".mean"]
                                      + (1 - BN_DECAY) * mean)
            new_state[u + ".var"] = (BN_DECAY * state[u + ".var"]
                                     + (1 - BN_DECAY) * var)
        else:
            mean, var = state[u + ".mean"], state[u + ".var"]
        x = (x - mean) / jnp.sqrt(var + BN_EPS) * params[u + ".g"] + params[u + ".b"]
        return stored(jax.nn.relu(x) if relu else x)

    x = unit(x, "stem", 2, True)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for stage, _, blocks, stride in STAGES:
        for b in range(blocks):
            u = f"s{stage}.{b}."
            s = stride if b == 0 else 1

            def block(x, u=u, s=s, b=b):
                y = unit(x, u + "a", s, True)
                y = unit(y, u + "b", 1, True)
                y = unit(y, u + "c", 1, False)
                sc = unit(x, u + "sc", s, False) if b == 0 else x
                return stored(jax.nn.relu(y + sc))

            # new_state is filled while tracing; under jax.checkpoint the
            # running statistics must leave the block as outputs
            def block_out(x, block=block, u=u, b=b):
                before = set(new_state)
                y = block(x)
                made = {k: new_state[k] for k in new_state if k not in before}
                return y, made

            if train:
                x, made = jax.checkpoint(block_out)(x)
                new_state.update(made)
            else:
                x = block(x)
    x = x.mean((1, 2))
    logits = mm(x, params["fc.w"]) + params["fc.b"]
    return logits, (new_state if train else state)


def logits_fn(params, state, x, cfg, operand=None):
    """Inference: running statistics, no state change."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, state, x, cfg, False, operand)[0]


def calibrated_state(params, x, cfg) -> dict:
    """Running statistics a trained model would carry: the batch statistics
    of a seeded calibration batch, layer by layer (decay 0)."""
    zero = {k: jnp.zeros(s, jnp.float32) for k, s in state_shapes(cfg).items()}
    with jax.default_matmul_precision("highest"):
        _, st = _forward(params, zero, x, cfg, True)
    return {k: v / (1 - BN_DECAY) for k, v in st.items()}


def loss_sum(params, state, x, labels, cfg, operand=None):
    logits, new_state = _forward(params, state, x, cfg, True, operand)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return -picked.sum(), new_state


def loss_count(x) -> int:
    return x.shape[0]


ROWS_PER_BLOCK = None
COUPLED_ROWS = True      # batch statistics couple the rows


def penalty(params, cfg):
    l2 = cfg["l2"]
    return 0.5 * l2 * sum(jnp.sum(v * v) for k, v in params.items()
                          if k.endswith(".w"))


def optimizer(cfg: dict):
    return common.Nesterovs(**cfg["optimizer"]["args"])
