"""Plain reference for the `qwen3-next-80b-a3b-l4` configuration.

Qwen3-Next (`Qwen/Qwen3-Next-80B-A3B-Instruct`, `config.json`): a decoder of
pre-norm blocks `h = x + mixer(rms(x)); y = h + moe(rms(h))`, whose mixer is
a gated delta rule (linear attention with a matrix state) in three layers of
four and a gated softmax attention in the fourth, and whose feed-forward is
512 routed SwiGLU experts, ten a token, beside one gated shared expert.
Written in float32 `jax.numpy` at matmul precision "highest" from the layer
equations of ISSUE 27 / the family's published modelling code; it imports
nothing of `deeplearning4j_tpu` and takes no array the program made.

  norm      rms(x; w) = x rsqrt(mean x^2 + eps) (1 + w)          (w starts 0)
  attention [q | g | k | v] = x Wqkv  (16 x 256, 16 x 256, 2 x 256, 2 x 256);
            q, k <- rms over each head's 256; rotary on the first 64 of
            each head (pairs (j, j + 32), angle pos theta^(-2j/64)); each
            key/value head serves 8 query heads; causal softmax at
            256^-0.5; o <- o sigmoid(g); y = o Wo
  delta     [q | k | v | z] = x Wqkvz (16 x 128, 16 x 128, 32 x 128, 32 x 128);
            [b | a] = x Wba; [q | k | v] <- silu(causal depthwise conv, width
            4); beta = sigmoid(b); g = -exp(A_log) softplus(a + dt_bias);
            q, k L2-normalised over 128, q <- q 128^-0.5; each key head
            serves 2 value heads. Per value head, S [128 x 128] from 0:
              S <- exp(g_t) S;  r = S^T k_t;  S <- S + k_t (beta_t (v_t - r))^T
              o_t = S^T q_t
            run TOKEN BY TOKEN here (the program runs it in chunks of 64);
            y = (w_n o rsqrt(mean o^2 + eps)) silu(z), then y Wout
  experts   p = softmax(x Wr) over all 512; the 10 largest, renormalised
            to sum 1 over the 10 chosen wherever they live; expert e:
            (silu(x Wg_e) (x Wu_e)) Wd_e; moe = sum over the chosen experts
            HELD HERE of p_e expert_e(x) + sigmoid(x ws) shared(x).

The weight layouts are flat concatenations ([q | g | k | v], [q | k | v | z],
[gate | up]) where the published checkpoint interleaves per head: a
relabelling of columns, the same function class.

The share (model-configs section 4): `num_experts` of the file is the count
HELD by this rank (experts `experts_first` .. + count of the published
`num_experts_published`); the router keeps its published width; what the
absent experts would add is left out, here and in the program alike.

Controls (the `operand` argument): "float8_e4m3fn" rounds the operands of
every product; "drop_carry" zeroes the delta rule's state at every chunk
boundary (what a chunked scan that loses its carry computes);
"drop_expert" leaves the first held expert's terms out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import common

# ---------------------------------------------------------------------------
# Limits of the comparison, each set from readings on one v5e at the cell's
# own size (2 x 8192 tokens, published widths; PR 27: the program's gaps are
# twelve seeds' [check] / PROGRAM lines, twelve more seeds held the limits;
# controls:
# benchmark/tests/read_limits_ids.py on 3 seeds; PERF.md section 2):
#   loss_gap        sound <= 4.4e-5; float8 control 3.9e-5 .. 3.3e-4: the
#                   loss at seeded weights hardly moves with precision, so it
#                   takes the accepted cells' limit, which leaves the sound
#                   runs' largest 4.5 x of room; the dropped carry reads
#                   3.6e-4 and 1.3e-3 and fails it.
#   grad_norm_gap   sound 7.2e-4 .. 3.5e-3 (the larger ones all on a delta
#                   layer's output-norm weight: the leaf behind the chunked
#                   scan); float8 control 0.0143, 0.0253, 0.0339; dropped
#                   carry 0.087, 0.142. The number the lower precision
#                   fails: the limit lies between the two, 2.3 x above
#                   the sound runs' largest (1.9 x after the review round) (they range over 5 x, and
#                   every later check draws new seeds) and 1.8 x below the
#                   control's smallest.
#   delta_norm_gap  sound 3.6e-4 .. 1.14e-3; float8 control 1.6e-3 .. 6.7e-3
#                   (not cleanly apart from sound), dropped carry 0.011,
#                   0.012. Held against a step that returns its state
#                   unchanged (1.0), with the room above the reading: 4.4 x the
#                   sound runs' largest, 200 x below 1.
# Review round, thirteen more seeds and one more of each control, compared
# under these LIMITS themselves (read_limits_ids.py prints `correct`):
# sound loss <= 3.9e-5, gradient 1.7e-3 .. 4.13e-3, change 3.2e-4 .. 1.0e-3;
# float8 2.2e-4 / 0.0209 / 1.9e-3, dropped carry 1.1e-3 / 0.163 / 0.0105,
# half a batch left out 1.06e-3 / 0.450 / 0.165: each control `correct` false.
# ---------------------------------------------------------------------------
LIMITS = {"loss_gap": 2.0e-4, "grad_norm_gap": 8.0e-3, "delta_norm_gap": 5.0e-3}
COMPARISONS = common.WORST_LEAF
CONTROL = "float8_e4m3fn"
CHUNK = 64               # the program's chunk; the "drop_carry" control's too
SEGMENT = 64             # tokens per checkpointed segment of the recurrence


def is_attention(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


def _dims(cfg):
    return dict(
        d=cfg["hidden_size"], v=cfg["vocab_size"], n=cfg["num_hidden_layers"],
        h=cfg["num_attention_heads"], kv=cfg["num_key_value_heads"],
        hd=cfg["head_dim"], hk=cfg["linear_num_key_heads"],
        hv=cfg["linear_num_value_heads"], dk=cfg["linear_key_head_dim"],
        dv=cfg["linear_value_head_dim"], cw=cfg["linear_conv_kernel_dim"],
        e=cfg["num_experts"], e_all=cfg["num_experts_published"],
        f=cfg["moe_intermediate_size"], fs=cfg["shared_expert_intermediate_size"])


def leaf_shapes(cfg: dict) -> dict:
    s = _dims(cfg)
    d = s["d"]
    shapes = {"embed": (s["v"], d)}
    for i in range(s["n"]):
        p = f"l{i}."
        shapes[p + "norm1"] = (d,)
        if is_attention(cfg, i):
            shapes.update({
                p + "attn.wqkv": (d, (2 * s["h"] + 2 * s["kv"]) * s["hd"]),
                p + "attn.qnorm": (s["hd"],), p + "attn.knorm": (s["hd"],),
                p + "attn.wo": (s["h"] * s["hd"], d)})
        else:
            key, val = s["hk"] * s["dk"], s["hv"] * s["dv"]
            shapes.update({
                p + "delta.wqkvz": (d, 2 * key + 2 * val),
                p + "delta.wba": (d, 2 * s["hv"]),
                p + "delta.conv": (s["cw"], 2 * key + val),
                p + "delta.a_log": (s["hv"],), p + "delta.dt_bias": (s["hv"],),
                p + "delta.norm": (s["dv"],),
                p + "delta.wout": (val, d)})
        shapes.update({
            p + "norm2": (d,),
            p + "moe.router": (d, s["e_all"]),
            p + "moe.wgu": (s["e"], d, 2 * s["f"]),
            p + "moe.wd": (s["e"], s["f"], d),
            p + "moe.shared_wgu": (d, 2 * s["fs"]),
            p + "moe.shared_wd": (s["fs"], d),
            p + "moe.shared_gate": (d, 1)})
    shapes["final_norm"] = (d,)
    shapes["head"] = (d, s["v"])
    return shapes


_BLOCK_LEAF = {
    "norm1": ("norm1", "w"), "norm2": ("norm2", "w"),
    "attn.wqkv": ("mixer", "Wqkv"), "attn.qnorm": ("mixer", "q_norm"),
    "attn.knorm": ("mixer", "k_norm"), "attn.wo": ("mixer", "Wo"),
    "delta.wqkvz": ("mixer", "Wqkvz"), "delta.wba": ("mixer", "Wba"),
    "delta.conv": ("mixer", "conv"), "delta.a_log": ("mixer", "A_log"),
    "delta.dt_bias": ("mixer", "dt_bias"), "delta.norm": ("mixer", "norm"),
    "delta.wout": ("mixer", "Wout"),
    "moe.router": ("moe", "router"), "moe.wgu": ("moe", "Wgu"),
    "moe.wd": ("moe", "Wd"), "moe.shared_wgu": ("moe", "shared_Wgu"),
    "moe.shared_wd": ("moe", "shared_Wd"),
    "moe.shared_gate": ("moe", "shared_gate"),
}


def program_paths(cfg: dict) -> dict:
    """Reference leaf -> leaf of `MultiLayerNetwork.params`: layer_0 the
    embedding, layer_{1+i} block i, then the final norm and the head."""
    n = cfg["num_hidden_layers"]
    out = {}
    for name in leaf_shapes(cfg):
        if name == "embed":
            out[name] = ("layer_0", "W")
        elif name == "final_norm":
            out[name] = (f"layer_{n + 1}", "w")
        elif name == "head":
            out[name] = (f"layer_{n + 2}", "W")
        else:
            blk, rest = name.split(".", 1)
            out[name] = (f"layer_{1 + int(blk[1:])}",) + _BLOCK_LEAF[rest]
    return out


def init_params(cfg: dict, seed: int) -> dict:
    """Seeded weights in one jitted call. Matrices and embeddings N(0, 0.02);
    zero-centred norm weights N(0, 0.02) and the delta rule's output norm
    1 + N(0, 0.02) (not exactly 0 / 1, so that a leaf installed in the
    wrong place shows); the short convolution N(0, 0.3) (the family's
    Conv1d default is U(-0.5, 0.5)); `A_log`, `dt_bias` such that the
    per-token decay exp(g) lies in about [0.9, 0.9999] (`assumed` in the
    configuration file: exp(A_log) ~ U(0.05, 0.3), dt_bias ~ U(-4, -2))."""
    shapes = leaf_shapes(cfg)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if name.endswith("a_log"):
                out[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32, 0.05, 0.3))
            elif name.endswith("dt_bias"):
                out[name] = jax.random.uniform(k, shape, jnp.float32, -4.0, -2.0)
            elif name.endswith("delta.conv"):
                out[name] = 0.3 * jax.random.normal(k, shape, jnp.float32)
            elif name.endswith("delta.norm"):
                out[name] = 1.0 + 0.02 * jax.random.normal(k, shape, jnp.float32)
            else:
                out[name] = 0.02 * jax.random.normal(k, shape, jnp.float32)
        return out

    return jax.jit(make)(common.seed_key(seed))


def init_state(cfg: dict, seed: int) -> dict:
    return {}


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------
def rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def rotary(x, cfg):
    """x [t, heads, head_dim]: the first `partial_rotary_factor` of each
    head rotated, half-split pairing."""
    t, hd = x.shape[0], x.shape[-1]
    r = int(hd * cfg["partial_rotary_factor"])
    j = jnp.arange(r // 2, dtype=jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * cfg["rope_theta"] ** (-2.0 * j / r)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def attention(p, x, cfg, mm):
    """x [t, d] of one sequence -> [t, d]."""
    s = _dims(cfg)
    t, h, kv, hd, eps = x.shape[0], s["h"], s["kv"], s["hd"], cfg["rms_norm_eps"]
    z = mm(x, p["wqkv"])
    q, g, k, v = jnp.split(z, [h * hd, 2 * h * hd, (2 * h + kv) * hd], axis=-1)
    q = rotary(rms(q.reshape(t, h, hd), p["qnorm"], eps), cfg)
    k = rotary(rms(k.reshape(t, kv, hd), p["knorm"], eps), cfg)
    v = v.reshape(t, kv, hd)
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(qh, kh, vh):
        sc = mm(qh, kh.T) * hd ** -0.5
        return mm(jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1), vh)

    # one head at a time (a scan, so that no two heads' [t, t] scores are
    # alive together); each key/value head serves h / kv query heads
    rep = lambda a: jnp.repeat(jnp.moveaxis(a, 1, 0), h // kv, axis=0)  # noqa: E731
    o = lax.map(lambda a: head(*a), (jnp.moveaxis(q, 1, 0), rep(k), rep(v)))
    o = jnp.moveaxis(o, 0, 1)                               # [t, h, hd]
    o = o.reshape(t, h * hd) * jax.nn.sigmoid(g)
    return mm(o, p["wo"])


def delta_recurrence(q, k, v, g, beta, drop_carry=False):
    """q, k [t, hv, dk], v [t, hv, dv], g, beta [t, hv] -> o [t, hv, dv],
    token by token; every SEGMENT tokens are one checkpoint."""
    t, hv, dk = q.shape
    dv = v.shape[-1]
    pad = (-t) % SEGMENT
    if pad:
        q, k, v = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, pad), (0, 0))) for a in (g, beta))
    pos = jnp.arange(t + pad)

    def token(S, inp):
        qt, kt, vt, gt, bt, i = inp
        if drop_carry:
            S = jnp.where(i % CHUNK == 0, 0.0, S)
        S = S * jnp.exp(gt)[:, None, None]
        r = jnp.einsum("hkv,hk->hv", S, kt, precision=common.HIGHEST)
        S = S + kt[:, :, None] * (bt[:, None] * (vt - r))[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=common.HIGHEST)

    @jax.checkpoint
    def segment(S, inp):
        return lax.scan(token, S, inp, unroll=16)    # fewer trips of the loop

    seg = lambda a: a.reshape((-1, SEGMENT) + a.shape[1:])  # noqa: E731
    _, o = lax.scan(segment, jnp.zeros((hv, dk, dv), jnp.float32),
                    tuple(seg(a) for a in (q, k, v, g, beta, pos)))
    return o.reshape((t + pad, hv, dv))[:t]


def delta(p, x, cfg, mm, drop_carry=False):
    """x [t, d] of one sequence -> [t, d]."""
    s = _dims(cfg)
    t, hk, hv, dk, dv = x.shape[0], s["hk"], s["hv"], s["dk"], s["dv"]
    key, val = hk * dk, hv * dv
    qkvz = mm(x, p["wqkvz"])
    qkv, z = qkvz[:, :2 * key + val], qkvz[:, 2 * key + val:]
    b, a = jnp.split(mm(x, p["wba"]), 2, axis=-1)
    w = p["conv"]                                     # [width, channels]
    cw = w.shape[0]
    padded = jnp.pad(qkv, ((cw - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[j:j + t] * w[j] for j in range(cw)))
    q, k, v = jnp.split(qkv, [key, 2 * key], axis=-1)
    q, k = q.reshape(t, hk, dk), k.reshape(t, hk, dk)
    l2 = lambda a: a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = l2(q) * dk ** -0.5, l2(k)
    q, k = (jnp.repeat(a, hv // hk, axis=1) for a in (q, k))
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(a + p["dt_bias"])
    o = delta_recurrence(q, k, v.reshape(t, hv, dv), g, beta, drop_carry)
    o = p["norm"] * o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                                  + cfg["rms_norm_eps"])
    y = o.reshape(t, val) * jax.nn.silu(z)
    return mm(y, p["wout"])


def swiglu(x, wgu, wd, mm):
    gate, up = jnp.split(mm(x, wgu), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, wd)


def moe(p, x, cfg, mm, held=None, skip=()):
    """x [n, d] -> [n, d]: the terms of the experts held (`held` =
    (first, count), default the configuration's share) plus the gated
    shared expert. Every held expert is computed on every token and
    weighted by its (possibly zero) routing weight: plain, not fast."""
    first, count = held if held else (cfg.get("experts_first", 0), cfg["num_experts"])
    probs = jax.nn.softmax(mm(x, p["router"]), axis=-1)
    top, idx = lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)

    def one(acc, e):
        wgu, wd, j = e
        wt = jnp.sum(jnp.where(idx == first + j, top, 0.0), axis=-1)
        for gone in skip:
            wt = jnp.where(j == gone, 0.0, wt)
        term = jax.checkpoint(
            lambda x_, a, b, w: w[:, None] * swiglu(x_, a, b, mm))(x, wgu, wd, wt)
        return acc + term, None

    routed, _ = lax.scan(one, jnp.zeros_like(x),
                         (p["wgu"], p["wd"], jnp.arange(count)))
    shared = jax.nn.sigmoid(mm(x, p["shared_gate"])) * swiglu(
        x, p["shared_wgu"], p["shared_wd"], mm)
    return routed + shared


def _sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def block(params, x, cfg, i, operand=None):
    """One decoder block on one sequence x [t, d]."""
    mm = common.matmul(operand if operand == CONTROL else None)
    eps = cfg["rms_norm_eps"]
    p = _sub(params, f"l{i}.")
    a = rms(x, p["norm1"], eps)
    if is_attention(cfg, i):
        h = x + attention(_sub(p, "attn."), a, cfg, mm)
    else:
        h = x + delta(_sub(p, "delta."), a, cfg, mm, operand == "drop_carry")
    skip = (0,) if operand == "drop_expert" else ()
    return h + moe(_sub(p, "moe."), rms(h, p["norm2"], eps), cfg, mm, skip=skip)


def hidden(params, row, cfg, operand=None):
    """[t] int32 ids of one sequence -> [t, d] after the final norm; every
    block is one checkpoint."""
    x = params["embed"][row]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda p, x_, i=i: block(p, x_, cfg, i, operand))(params, x)
    return rms(x, params["final_norm"], cfg["rms_norm_eps"])


def logits_fn(params, ids, cfg, operand=None):
    """[b, t] int32 ids -> [b, t, V] float32 logits, a sequence at a time."""
    mm = common.matmul(operand if operand == CONTROL else None)
    return jnp.stack([mm(hidden(params, row, cfg, operand), params["head"])
                      for row in ids])


LOSS_ROWS = 2048         # tokens whose logits exist at a time


def row_loss(params, row, labels, cfg, operand=None):
    """Sum of next-token cross-entropies of one sequence, the head and the
    log-softmax LOSS_ROWS tokens at a time."""
    mm = common.matmul(operand if operand == CONTROL else None)
    h = hidden(params, row, cfg, operand)

    @jax.checkpoint
    def part(hb, lb, head):
        logp = jax.nn.log_softmax(mm(hb, head), axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=-1).sum()

    t = h.shape[0]
    n = t // LOSS_ROWS if t % LOSS_ROWS == 0 else 1
    parts = lax.map(lambda a: part(a[0], a[1], params["head"]),
                    (h.reshape(n, t // n, -1), labels.reshape(n, t // n)))
    return parts.sum()


def loss_sum(params, state, ids, labels, cfg, operand=None):
    """Sum (not mean) of the cross-entropies of a block of rows; every row
    is one checkpoint and the rows are a scan, so the backward holds one
    sequence's activations."""
    one = jax.checkpoint(lambda p, r, l: row_loss(p, r, l, cfg, operand))
    return lax.map(lambda a: one(params, a[0], a[1]), (ids, labels)).sum(), state


def loss_count(ids) -> int:
    return ids.shape[0] * ids.shape[1]


ROWS_PER_BLOCK = 1
COUPLED_ROWS = False
penalty = None


def optimizer(cfg: dict):
    return common.Adam(**cfg["optimizer"]["args"])


# ---------------------------------------------------------------------------
# the reference's steps, lean: 626 M float32 parameters with their gradient
# and Adam's two moments are 10 GB of the chip's 16, so the starting weights
# stay on the host and Adam runs leaf by leaf
# ---------------------------------------------------------------------------
def train_steps(mod, cfg, params0, state0, batches, operand=None):
    """`common.train_steps` with the same result, for weights that fit the
    chip once but not five times. The whole batch's gradient comes from one
    call (rows are a scan of checkpoints: weights, the gradient, its scan
    carry and one row's activations are live, 13.5 GB by
    `memory_analysis()`), so Adam's two moments wait on the HOST meanwhile
    and visit the chip leaf by leaf. `params0`: host (numpy) arrays."""
    def grad(params, x, y):
        def f(p):
            with jax.default_matmul_precision("highest"):
                return loss_sum(p, {}, x, y, cfg, operand)[0]
        return jax.value_and_grad(f)(params)

    grad = jax.jit(grad)
    opt = optimizer(cfg)
    params = {k: jnp.asarray(v) for k, v in params0.items()}
    moments = None                                      # (m, v) on the host
    losses, grad_norms = [], {}
    norm = lambda a: float(jnp.sqrt(jnp.sum(jnp.square(a))))  # noqa: E731
    for i, (x, y) in enumerate(batches):
        total, grads = grad(params, jnp.asarray(x), jnp.asarray(y))
        count = loss_count(x)
        losses.append(float(total) / count)
        m, v = jax.device_put(moments) if moments else ({}, {})
        for k in list(params):
            g = grads.pop(k) / count
            if i == 0:
                grad_norms[k] = norm(g)
                m[k] = v[k] = jnp.zeros_like(g)
            new, st = opt.apply({k: params[k]}, {k: g},
                                {"m": {k: m[k]}, "v": {k: v[k]}, "t": i})
            params[k], m[k], v[k] = new[k], st["m"][k], st["v"][k]
        moments = jax.device_get((m, v)) if i + 1 < len(batches) else None
        del m, v
    delta_norms = {k: norm(params[k] - np.asarray(params0[k])) for k in params}
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta_norms}
