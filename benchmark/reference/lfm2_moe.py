"""Plain reference for `lfm2_moe` configurations (`lfm2-24b-a2b-l5`).

`lfm2_moe` (`LiquidAI/LFM2-24B-A2B`, `config.json`): a decoder of pre-norm
layers with two residuals each, `h = x + operator(rms(x))`,
`y = h + ffn(rms(h))`. Published layer i (from 0) mixes by `layer_types[i]`:
"conv", a short causal convolution between two gates — no recurrence, no
softmax, no state beyond its last two tokens —, or "full_attention",
grouped-query softmax attention with an RMS norm on every head of q and k
and rotary positions over the whole head. Its feed-forward is a dense swiglu
for i < `num_dense_layers`, else 64 sigmoid-routed swiglu experts, four a
token, and NO shared expert. Written in float32 `jax.numpy` at matmul
precision "highest" from the layer equations of ISSUE 40; it imports nothing
of `deeplearning4j_tpu` and takes no array the program made.

  norm      rms(x; w) = x rsqrt(mean x^2 + eps) w         (plain weight, from 1)
  conv      [B | C | z] = x Win (3 x 2048, split in that order); u = B z;
            c_t = sum_{s = 0..2} taps[2 - s] u_{t-s}, an explicit sum over the
            three taps on [t, f], zeros before the sequence, no bias, no
            activation; out = (C c) Wout
  attention [q | k | v] = x Wqkv: 32 query heads, 8 key/value heads of 64;
            q and k normalised a head (rms over the 64, plain weights
            q_norm, k_norm), then rotated over the WHOLE head: pair j =
            features (j, j + 32), by the angle p theta^(-2j / 64) at position
            p = the token's index, (a, b) -> (a cos - b sin, a sin + b cos);
            key/value head g serves query heads 4g .. 4g + 3; causal softmax
            at 64^-0.5, materialised, in query blocks; out = concat_heads(o) Wo
  dense     (silu(x Wg) (x Wu)) Wd, [gate | up] one matrix of 2 x 11776
  experts   s = sigmoid(u Wr) over all 64; CHOSEN: the 4 largest of s +
            expert_bias (the bias chooses, it does not weigh); weights = s at
            the chosen / (their sum + 1e-6) x routed_scaling_factor 1;
            expert e the swiglu of 1536; out = sum over the chosen experts
            HELD HERE of w_e expert_e(u). The choice is a dense 0/1 mask over
            the 64: no sort, no buffer.

Flat layouts where the published checkpoint has separate matrices, each a
relabelling: [gate | up] (`w1`, `w3`), [q | k | v] (`q_proj`, `k_proj`,
`v_proj`); `taps` [3, f] is `conv.weight` [f, 1, 3] transposed; Win, Wout are
`in_proj`, `out_proj` transposed.

The share (model-configs section 4): `num_experts` of the file is the count
HELD by this rank (experts `experts_first` .. + count of the published
`num_experts_published`); the router keeps its published width; what the
absent experts would add is left out, here and in the program alike. The
layers built are the published layers `layers_first` .. + `num_hidden_layers`.

Controls (the `operand` argument), each a whole reference: "float8_e4m3fn"
rounds the operands of every product; the three faults of the new
mathematics: "drop_taps" keeps the convolution's current-token tap alone,
"swap_bc" reads the split as [C | B | z], "drop_rope" rotates nothing; and
"drop_expert" leaves the first held expert's terms out.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import common

# ---------------------------------------------------------------------------
# Limits of the comparison, each set from readings on one v5e at the cell's
# own size (2 x 8192 tokens, published widths; my chip runs, PR 40: SOUND = ten
# seeds, the [check] lines of eight runs — 2147506001, -002 traced, -021 .. -026
# — and benchmark/tests/read_leaf_gaps_ids.py on seeds 2147506011 and 911,
# which reads every leaf; the four controls the same script on those two
# seeds, each a whole reference; PERF.md section 2 has the table and the runs
# made after the limits were set):
#   loss_gap        sound 3.0e-7 .. 2.9e-5 (30 step losses); float8 1.3e-6 ..
#                   1.4e-4 (not apart); one tap 2.9e-5 .. 1.2e-3 and [C | B | z]
#                   2.9e-4 .. 1.3e-3 fail it on both seeds; no rotation
#                   <= 2.1e-5: the loss at seeded weights hardly moves with the
#                   precision or with one layer's positions, so it takes the
#                   accepted cells' limit, which leaves the readings 6.9 x.
#   grad_norm_gap   worst leaf, against gross faults. Sound 2.2e-3 .. 1.56e-2,
#                   in eight of ten runs `l0.norm1`: a pre-norm's weight
#                   gradient has ONE large component, the constant channel's,
#                   that no sum over channels averages (PERF.md section 7,
#                   PR 38 (a)); every other leaf is below 3.1e-3. float8 3.2e-2,
#                   4.5e-2 on the SAME leaf: THIS NUMBER CANNOT PART THE
#                   PRECISIONS, the median below does. No rotation 7.4e-2,
#                   0.155 (`l1.attn.wo`), one tap 0.35, 0.64, [C | B | z] 0.24,
#                   0.48. The limit is 3.2 x the sound maximum, 1.5 x below no
#                   rotation's smaller reading, 4.8 x below the other faults'.
#   grad_norm_gap_median  the MEDIAN leaf: the number the lower precision
#                   fails. Sound 8.5e-5 .. 1.92e-4 (mean 1.36e-4, standard
#                   deviation 3.3e-5); float8 2.98e-3, 8.9e-3 = 15 x and 46 x
#                   the sound maximum; one tap 8.1e-2, 8.2e-2; [C | B | z]
#                   5.4e-2, 7.7e-2; no rotation 1.8e-4, 2.8e-4 (one layer in
#                   five: the median does not see it, the two worst-leaf
#                   numbers do). The limit is 3.1 x the sound maximum (14
#                   standard deviations above the mean), 5 x below float8's
#                   smaller reading.
#   delta_norm_gap  worst leaf. Sound 4.8e-4 .. 1.14e-3 (a router or a
#                   feed-forward's pre-norm); float8 2.1e-3, 4.1e-3 (not
#                   apart: Adam normalises the step); no rotation 1.7e-2,
#                   3.05e-2 (`l1.attn.wqkv`), [C | B | z] 1.7e-2, 1.7e-2, one
#                   tap 0.10, 0.10 (the taps themselves). Held against a step
#                   that returns its state unchanged (1.0) with the room above
#                   the reading: 3.5 x the sound maximum, 4.3 x below no
#                   rotation's smaller reading, 250 x below 1.
# ---------------------------------------------------------------------------
LIMITS = {"loss_gap": 2.0e-4, "grad_norm_gap": 5.0e-2, "grad_norm_gap_median": 6.0e-4,
          "delta_norm_gap": 4.0e-3}
COMPARISONS = common.WORST_LEAF + (("grad_norm_gap_median", "grad_norms", "median", None),)
CONTROL = "float8_e4m3fn"
QUERY_BLOCK = 1024       # queries whose [block, t] scores exist at a time
LOSS_ROWS = 2048         # tokens whose logits exist at a time
F32 = jnp.float32


def kinds(cfg: dict):
    """(mixer, feed-forward) of each layer built: ("conv" | "attention",
    "dense" | "moe"), the published layers `layers_first` .. on."""
    first = cfg.get("layers_first", 0)
    return [("conv" if cfg["layer_types"][i] == "conv" else "attention",
             "dense" if i < cfg["num_dense_layers"] else "moe")
            for i in range(first, first + cfg["num_hidden_layers"])]


def _dims(cfg):
    h = cfg["num_attention_heads"]
    return dict(
        d=cfg["hidden_size"], v=cfg["vocab_size"], cw=cfg["conv_L_cache"],
        h=h, kv=cfg["num_key_value_heads"], hd=cfg["hidden_size"] // h,
        ff=cfg["intermediate_size"],
        e=cfg["num_experts"], e_all=cfg["num_experts_published"],
        f=cfg["moe_intermediate_size"])


def leaf_shapes(cfg: dict) -> dict:
    s = _dims(cfg)
    d = s["d"]
    shapes = {"embed": (s["v"], d)}
    for i, (mixer_kind, ffn_kind) in enumerate(kinds(cfg)):
        p = f"l{i}."
        shapes[p + "norm1"] = (d,)
        if mixer_kind == "conv":
            shapes.update({p + "conv.win": (d, 3 * d), p + "conv.taps": (s["cw"], d),
                           p + "conv.wout": (d, d)})
        else:
            shapes.update({
                p + "attn.wqkv": (d, (s["h"] + 2 * s["kv"]) * s["hd"]),
                p + "attn.q_norm": (s["hd"],), p + "attn.k_norm": (s["hd"],),
                p + "attn.wo": (s["h"] * s["hd"], d)})
        shapes[p + "norm2"] = (d,)
        if ffn_kind == "dense":
            shapes.update({p + "mlp.wgu": (d, 2 * s["ff"]), p + "mlp.wd": (s["ff"], d)})
        else:
            shapes.update({
                p + "moe.router": (d, s["e_all"]), p + "moe.select_bias": (s["e_all"],),
                p + "moe.wgu": (s["e"], d, 2 * s["f"]), p + "moe.wd": (s["e"], s["f"], d)})
    shapes["final_norm"] = (d,)
    shapes["head"] = (d, s["v"])
    return shapes


#: reference leaf of a layer -> (which of the layer's two blocks, the leaf
#: inside `SubLayerBlock`'s params)
_BLOCK_LEAF = {
    "norm1": (0, "norm", "w"), "norm2": (1, "norm", "w"),
    "conv.win": (0, "sub", "Win"), "conv.taps": (0, "sub", "conv"),
    "conv.wout": (0, "sub", "Wout"),
    "attn.wqkv": (0, "sub", "Wqkv"), "attn.q_norm": (0, "sub", "q_norm"),
    "attn.k_norm": (0, "sub", "k_norm"), "attn.wo": (0, "sub", "Wo"),
    "mlp.wgu": (1, "sub", "Wgu"), "mlp.wd": (1, "sub", "Wd"),
    "moe.router": (1, "sub", "router"), "moe.select_bias": (1, "sub", "select_bias"),
    "moe.wgu": (1, "sub", "Wgu"), "moe.wd": (1, "sub", "Wd"),
}


def program_paths(cfg: dict) -> dict:
    """Reference leaf -> leaf of `MultiLayerNetwork.params`: layer_0 the
    embedding, layer_{1+2i} and layer_{2+2i} the mixer's and the
    feed-forward's block of layer i, then the final norm and the head."""
    n = cfg["num_hidden_layers"]
    out = {}
    for name in leaf_shapes(cfg):
        if name == "embed":
            out[name] = ("layer_0", "W")
        elif name == "final_norm":
            out[name] = (f"layer_{2 * n + 1}", "w")
        elif name == "head":
            out[name] = (f"layer_{2 * n + 2}", "W")
        else:
            blk, rest = name.split(".", 1)
            which, *leaf = _BLOCK_LEAF[rest]
            out[name] = (f"layer_{1 + 2 * int(blk[1:]) + which}", *leaf)
    return out


def program_state_paths(cfg: dict) -> dict:
    """The reference keeps no state (the program's is its counters)."""
    return {}


#: what `init_params` adds to iid weights so that the ORDER of the tokens and
#: the ROLES of the convolution's three streams show in the numbers the
#: comparison reads (norms). Channel 0 of the hidden state is a CONSTANT (what
#: a trained model's massive-activation channels are, and the only way to a
#: bias in a model that has none): the embedding writes CHANNEL there for every
#: token, no matrix that writes to the residual stream touches it, and no
#: matrix reads it but the q and k columns of Wqkv and the B and z columns of
#: Win
CHANNEL = 1.0
#: row 0 of a key head's columns is ROPE_GAIN N(0, 1), of a query head's the
#: same vector turned back `look_back(head)` positions: after the heads' norms
#: the constant part holds most of a head's energy, and the token that far
#: back gets up to 64^0.5 = 8 more in its logit
ROPE_GAIN = 3.0
#: row 0 of the B and of the z columns of Win is CONV_GAIN N(0, 1) and of the C
#: columns zero: u = B z then has a token-independent part b0 z0 that the taps
#: add up COHERENTLY ((sum of taps)^2, not the sum of their squares), which a
#: gate outside the convolution does not have — [C | B | z] read for
#: [B | C | z] moves the mixer's output by tens of per cent, where over iid
#: weights the two orders have the same statistics
CONV_GAIN = 1.0
#: the taps are TAP_MEAN + TAP_STD N(0, 1): a trained short filter passes the
#: low frequencies, its taps share a sign; at N(0, 0.02) like a matrix the
#: mixer would add 1e-3 of what attention adds. The sum of their squares is
#: ~0.6, so a conv mixer's output has an rms of the order of the attention
#: layer's and of the embedding's
TAP_MEAN, TAP_STD = 0.4, 0.2
#: leaves whose row 0 (they read the hidden state) / column 0 (they write it)
#: is zero at the start
READS = ("conv.win", "attn.wqkv", "moe.router", "mlp.wgu", "moe.wgu", "head")
WRITES = ("conv.wout", "attn.wo", "mlp.wd", "moe.wd")


def look_back(head: int) -> int:
    return 1 + 64 * head


def init_params(cfg: dict, seed: int) -> dict:
    """Seeded weights in one jitted call. Matrices N(0, 0.02); embedding rows
    N(0, 1) (the hidden state then has an rms near 1 at the first layer, at
    every size); norm weights 1 + N(0, 0.02) (not exactly 1, so that a leaf
    installed in the wrong place shows); the selection bias N(0, 0.01), so
    that it changes some choices; the taps as above. Two things are NOT iid,
    because with iid weights over iid token ids every statistic of a step is
    the same whatever the rotation does (the scores are exchangeable over
    positions: PERF.md section 6, PR 38) and whichever of B and C gates
    inside the convolution: the constant channel, read by the attention
    layer's q and k columns (head h then prefers the token look_back(h)
    before it — what a trained previous-token head is; without the rotation
    the preference is gone) and by the conv mixers' B and z columns."""
    shapes = leaf_shapes(cfg)
    s = _dims(cfg)
    d, h, kv, hd = s["d"], s["h"], s["kv"], s["hd"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    turn = jnp.stack([rotate(jnp.eye(hd, dtype=F32), theta,
                             jnp.full((hd,), -float(look_back(i)), F32))
                      for i in range(h)])                   # [h, hd, hd]: row e_i turned back

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            normal = jax.random.normal(jax.random.fold_in(key, i), shape, F32)
            if name.endswith(("norm", "norm1", "norm2")):
                out[name] = 1.0 + 0.02 * normal
            elif name.endswith("select_bias"):
                out[name] = 0.01 * normal
            elif name.endswith("conv.taps"):
                out[name] = TAP_MEAN + TAP_STD * normal
            elif name == "embed":
                out[name] = normal.at[:, 0].set(CHANNEL)
            elif name.endswith(READS):
                out[name] = (0.02 * normal).at[..., 0, :].set(0.0)
            elif name.endswith(WRITES):
                out[name] = (0.02 * normal).at[..., 0].set(0.0)
            else:
                out[name] = 0.02 * normal
        for i, (mixer_kind, _) in enumerate(kinds(cfg)):
            fresh = jax.random.fold_in(key, len(shapes) + i)
            if mixer_kind == "conv":
                b0, z0 = CONV_GAIN * jax.random.normal(fresh, (2, d), F32) / CHANNEL
                row = jnp.concatenate([b0, jnp.zeros((d,), F32), z0])
                out[f"l{i}.conv.win"] = out[f"l{i}.conv.win"].at[0].set(row)
            else:
                u = ROPE_GAIN * jax.random.normal(fresh, (kv, hd), F32) / CHANNEL
                turned = jnp.einsum("hr,hrc->hc", jnp.repeat(u, h // kv, axis=0), turn,
                                    precision=common.HIGHEST)
                row = jnp.concatenate([turned.reshape(-1), u.reshape(-1),
                                       jnp.zeros((kv * hd,), F32)])
                out[f"l{i}.attn.wqkv"] = out[f"l{i}.attn.wqkv"].at[0].set(row)
        return out

    return jax.jit(make)(common.seed_key(seed))


def init_state(cfg: dict, seed: int) -> dict:
    return {}


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------
def rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate(a, theta: float, pos=None):
    """a [t, ..., r], token p at position p (or `pos[p]`): pair j =
    features (j, j + r/2) of the last axis turns by p theta^(-2j / r)."""
    t, r = a.shape[0], a.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(r // 2, dtype=F32) / r)
    pos = jnp.arange(t, dtype=F32) if pos is None else pos
    ang = pos.reshape((t,) + (1,) * (a.ndim - 1)) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x, y = a[..., :r // 2], a[..., r // 2:]
    return jnp.concatenate([x * cos - y * sin, x * sin + y * cos], axis=-1)


def short_conv(p, x, cfg, mm, order="bcz", taps=None):
    """x [t, d] of one sequence -> [t, d]. `order`: the split of x Win,
    "bcz" as published or a control's "cbz"; `taps`: how many of the taps
    count, from the current token's back (None: all)."""
    cw = p["taps"].shape[0]
    parts = dict(zip(order, jnp.split(mm(x, p["win"]), 3, axis=-1)))
    u = parts["b"] * parts["z"]
    c = jnp.zeros_like(u)
    for s in range(cw if taps is None else taps):     # the token s places back
        c = c + p["taps"][cw - 1 - s] * jnp.pad(u, ((s, 0), (0, 0)))[:u.shape[0]]
    return mm(parts["c"] * c, p["wout"])


def attention(p, x, cfg, mm, rope=True):
    """x [t, d] of one sequence -> [t, d]: a head and a block of queries at
    a time (a scan, so that no two blocks' scores are alive together)."""
    s = _dims(cfg)
    t, h, kv, hd = x.shape[0], s["h"], s["kv"], s["hd"]
    q, k, v = jnp.split(mm(x, p["wqkv"]), [h * hd, (h + kv) * hd], axis=-1)
    q = rms(q.reshape(t, h, hd), p["q_norm"], cfg["norm_eps"])
    k = rms(k.reshape(t, kv, hd), p["k_norm"], cfg["norm_eps"])
    if rope:
        theta = float(cfg["rope_parameters"]["rope_theta"])
        q, k = rotate(q, theta), rotate(k, theta)
    k, v = (jnp.repeat(a, h // kv, axis=1) for a in (k, v.reshape(t, kv, hd)))
    qb = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    pos = jnp.arange(t)

    @jax.checkpoint
    def block(qh, rows, kh, vh):
        sc = mm(qh, kh.T) * hd ** -0.5
        sc = jnp.where(rows[:, None] >= pos[None, :], sc, -jnp.inf)
        return mm(jax.nn.softmax(sc, axis=-1), vh)

    def head(a):
        qh, kh, vh = a
        o = lax.map(lambda b: block(b[0], b[1], kh, vh),
                    (qh.reshape(t // qb, qb, -1), pos.reshape(t // qb, qb)))
        return o.reshape(t, hd)

    o = lax.map(head, tuple(jnp.moveaxis(m, 1, 0) for m in (q, k, v)))
    return mm(jnp.moveaxis(o, 0, 1).reshape(t, h * hd), p["wo"])


def swiglu(x, wgu, wd, mm):
    gate, up = jnp.split(mm(x, wgu), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, wd)


def route(p, x, cfg, mm):
    """x [n, d] -> weights [n, experts]: zero but at the chosen."""
    s = jax.nn.sigmoid(mm(x, p["router"]))
    sel = s + p["select_bias"]
    chosen = sel >= lax.top_k(sel, cfg["num_experts_per_tok"])[0][:, -1:]   # a dense 0/1 mask
    w = jnp.where(chosen, s, 0.0)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    return w * cfg["routed_scaling_factor"]


def moe(p, x, cfg, mm, held=None, skip=()):
    """x [n, d] -> [n, d]: the terms of the experts held (`held` =
    (first, count), default the configuration's share); no shared expert.
    Every held expert is computed on every token and weighted by its
    (possibly zero) routing weight: plain, not fast."""
    first, count = held if held else (cfg.get("experts_first", 0), cfg["num_experts"])
    w = route(p, x, cfg, mm)[:, first:first + count]

    def one(acc, e):
        wgu, wd, wt, j = e
        for gone in skip:
            wt = jnp.where(j == gone, 0.0, wt)
        term = jax.checkpoint(
            lambda x_, a, b, w_: w_[:, None] * swiglu(x_, a, b, mm))(x, wgu, wd, wt)
        return acc + term, None

    out, _ = lax.scan(one, jnp.zeros_like(x), (p["wgu"], p["wd"], w.T, jnp.arange(count)))
    return out


def _sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def _mm(operand):
    return common.matmul(operand if operand == CONTROL else None)


def mixer(params, x, cfg, i, operand=None):
    """h = x + operator(rms(x)) of layer i on one sequence x [t, d]."""
    p = _sub(params, f"l{i}.")
    a = rms(x, p["norm1"], cfg["norm_eps"])
    if kinds(cfg)[i][0] == "conv":
        return x + short_conv(_sub(p, "conv."), a, cfg, _mm(operand),
                              order="cbz" if operand == "swap_bc" else "bcz",
                              taps=1 if operand == "drop_taps" else None)
    return x + attention(_sub(p, "attn."), a, cfg, _mm(operand), rope=operand != "drop_rope")


def ffn(params, h, cfg, i, operand=None):
    """y = h + ffn(rms(h)) of layer i."""
    mm = _mm(operand)
    p = _sub(params, f"l{i}.")
    a = rms(h, p["norm2"], cfg["norm_eps"])
    if kinds(cfg)[i][1] == "dense":
        return h + swiglu(a, p["mlp.wgu"], p["mlp.wd"], mm)
    return h + moe(_sub(p, "moe."), a, cfg, mm, skip=(0,) if operand == "drop_expert" else ())


def block(params, x, cfg, i, operand=None):
    """One layer on one sequence x [t, d]; each half is one checkpoint."""
    h = jax.checkpoint(lambda p, x_: mixer(p, x_, cfg, i, operand))(params, x)
    return jax.checkpoint(lambda p, h_: ffn(p, h_, cfg, i, operand))(params, h)


def hidden(params, row, cfg, operand=None):
    """[t] int32 ids of one sequence -> [t, d] after the final norm."""
    x = params["embed"][row]
    for i in range(cfg["num_hidden_layers"]):
        x = block(params, x, cfg, i, operand)
    return rms(x, params["final_norm"], cfg["norm_eps"])


def row_loss(params, row, labels, cfg, operand=None):
    """Sum of next-token cross-entropies of one sequence, the head and the
    log-softmax LOSS_ROWS tokens at a time (the head is a matrix of its own:
    `assumed`)."""
    mm = _mm(operand)
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
# the reference's steps, lean: 788 M float32 parameters with their gradient
# and Adam's two moments are 12.6 GB of the chip's 16, so the starting weights
# stay on the host and Adam runs leaf by leaf
# ---------------------------------------------------------------------------
def _adam_leaf(args: dict):
    """DL4J's AdamUpdater on one leaf (bias correction folded into the step
    size, epsilon added to sqrt(v)), every array float32 whatever
    `jax_enable_x64` says (`common.Adam`'s step size is a numpy float64,
    which widens the leaf where that flag is on: `tests/` switches it on)."""
    lr, b1, b2, eps = (args[k] for k in ("learning_rate", "beta1", "beta2", "epsilon"))

    @jax.jit
    def step(p, g, m, v, alpha):
        m = (b1 * m + (1 - b1) * g).astype(F32)
        v = (b2 * v + (1 - b2) * g * g).astype(F32)
        return (p - alpha * m / (jnp.sqrt(v) + eps)).astype(F32), m, v

    def apply(p, g, m, v, t: int):
        alpha = lr * math.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        return step(p, g.astype(F32), m, v, jnp.asarray(alpha, F32))

    return apply


def train_steps(mod, cfg, params0, state0, batches, operand=None):
    """`common.train_steps` with the same result, for weights that fit the
    chip once but not five times. A row's gradient comes from one call and
    the rows' are added leaf by leaf (weights, the running sum and one
    row's gradient and activations are live), so Adam's two moments wait on
    the HOST meanwhile and visit the chip one leaf at a time (all four
    arrays of 788 M parameters together would be 12.6 GB). `params0`: host
    (numpy) arrays."""
    def grad(params, x, y):
        def f(p):
            with jax.default_matmul_precision("highest"):
                return row_loss(p, x, y, cfg, operand)
        return jax.value_and_grad(f)(params)

    grad = jax.jit(grad)
    add = jax.jit(jnp.add, donate_argnums=0)
    adam = _adam_leaf(cfg["optimizer"]["args"])
    params = {k: jnp.asarray(v, F32) for k, v in params0.items()}
    m_host, v_host = {}, {}                             # Adam's moments, between steps
    losses, grad_norms = [], {}
    norm = lambda a: float(jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)))))  # noqa: E731
    for i, (x, y) in enumerate(batches):
        total, grads = 0.0, None
        for row, labels in zip(x, y):
            part, g = grad(params, jnp.asarray(row), jnp.asarray(labels))
            total += float(part)
            if grads is None:
                grads = g
            else:
                for k in list(g):
                    grads[k] = add(grads[k], g.pop(k))
        count = loss_count(x)
        losses.append(total / count)
        for k in list(params):
            g = grads.pop(k) / count
            if i == 0:
                grad_norms[k] = norm(g)
                m = v = jnp.zeros_like(g, F32)
            else:
                m, v = jnp.asarray(m_host.pop(k)), jnp.asarray(v_host.pop(k))
            params[k], m, v = adam(params[k], g, m, v, i + 1)
            if i + 1 < len(batches):
                m_host[k], v_host[k] = jax.device_get((m, v))
    delta_norms = {k: norm(params[k] - np.asarray(params0[k])) for k in params}
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta_norms}
