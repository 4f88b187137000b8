"""Plain reference for `laguna` configurations (`laguna-s-2.1-l5`).

`laguna` (`poolside/Laguna-S-2.1`, `config.json`): a decoder of pre-norm
layers with two residuals each, `a = x + attn(rms(x))`, `y = a + ffn(rms(a))`,
whose attention layers are NOT alike. Published layer l (from 0) is
`layer_types[l]`: "sliding_attention" — a query sees the `sliding_window` keys
up to and with its own — or "full_attention", the whole past; with
`num_attention_heads_per_layer[l]` query heads over `num_key_value_heads`
key/value heads of `head_dim`, the rotary recipe of its type and one sigmoid
gate a head and token. Its feed-forward is a dense swiglu for l in
`mlp_only_layers`, else softmax-routed swiglu experts beside a shared one.
Written in float32 `jax.numpy` at matmul precision "highest" from the layer
equations of ISSUE 47; it imports nothing of `deeplearning4j_tpu` and takes no
array the program made.

  norm      rms(x; w) = x rsqrt(mean x^2 + eps) w         (plain weight, from 1)
  attention [q | k | v] = x Wqkv: H query heads, KV key/value heads of 128; no
            q/k norm; positions over the first R features of every q and k
            head, pair j = features (j, j + R/2), position = the token's
            index from 0:
              sliding  R = 128 (partial_rotary_factor 1), angle p theta^(-2j/R)
              global   R = 64 (0.5; features 64 .. 127 pass through), yarn:
                       e_j = theta^(-2j/R); c(n) = R ln(original / (2 pi n)) /
                       (2 ln theta); lo = floor(c(beta_fast)), hi =
                       ceil(c(beta_slow)); r_j = clip((j - lo) / (hi - lo), 0,
                       1); f_j = e_j (1 - r_j) + (e_j / factor) r_j; angle
                       p f_j; cos and sin TIMES attention_factor
            head h reads key/value head h // (H / KV); scores q . k / sqrt(128);
            global: j <= i; sliding: i - window < j <= i (`window` keys, the
            query's own among them); softmax materialised in query blocks;
            g = sigmoid(x Wg), Wg [d, H]: one scalar a head and token;
            out = concat_heads(g_h o_h) Wo
  dense     (silu(x Wg) (x Wu)) Wd, [gate | up] one matrix of 2 x 12288
  experts   p = softmax(u Wr) over ALL experts; the top-k; weights = p at the
            chosen / their sum (norm_topk_prob) x moe_routed_scaling_factor;
            out = sum over the chosen experts HELD HERE of w_e expert_e(u) +
            shared(u), both swiglus of 1024, the shared one not gated. The
            choice is a dense 0/1 mask over the experts: no sort, no buffer.

Flat layouts where the published checkpoint has separate matrices, each a
relabelling: [gate | up], [q | k | v].

The share (model-configs section 4): `num_experts` of the file is the count
HELD by this rank (experts `experts_first` .. + count of the published
`num_experts_published`); `num_attention_heads_per_layer` and
`num_key_value_heads` are the head counts HELD (one tensor-parallel rank's
heads of each layer, in the published ratio); the router keeps its published
width; what the absent experts and the other rank's heads would add is left
out, here and in the program alike. The layers built are the published layers
`layers_first` .. + `num_hidden_layers`.

Controls (the `operand` argument), each a whole reference: "float8_e4m3fn"
rounds the operands of every product; the faults of the new mathematics:
"drop_window" (the sliding layers see the whole past), "window_511" (one key
fewer), "drop_yarn" (the global layers' frequencies plain theta^(-2j/R), the
factor kept), "drop_rope_scale" (the schedule kept, cos and sin not scaled),
"drop_gate" (no gate on the heads' outputs).
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
# own size (1 x 8192 tokens, published widths; my chip runs, PR 47: SOUND =
# the [check] lines of seven runs, seeds 2147506131 .. -136 and -137 traced;
# the six controls `benchmark/tests/read_limits_ids.py --control-only` on seed
# 2147506113, "window_511" on 913 too, each a whole reference; PERF.md section
# 2 has the table and the runs made after the limits were set):
#   loss_gap        sound 9.2e-6 .. 4.5e-5 (21 step losses); float8 2.7e-4, a
#                   window one key short 3.6e-4, 8.7e-4, no yarn 6.2e-4, no
#                   window 7.8e-4: the accepted cells' limit leaves the
#                   readings 4.5 x and every control but the gate's fails it.
#   grad_norm_gap   worst leaf, against gross faults. Sound 4.2e-3 .. 9.0e-3
#                   (a pre-norm's weight or an attention matrix); float8
#                   2.6e-2: THIS NUMBER DOES NOT PART THE PRECISIONS, the
#                   median below does. A window one key short 6.3e-2, 8.9e-2;
#                   no yarn 6.3e-2; no window 0.25; no factor on the tables
#                   0.78; no gate 1.97. The limit is 3.3 x the sound maximum
#                   and 2.1 x below the structural controls' smallest.
#   grad_norm_gap_median  the MEDIAN leaf: the number the lower precision
#                   fails. Sound 2.8e-4 .. 4.4e-4 (mean 3.3e-4, standard
#                   deviation 5.3e-5: the edge heads' logits of 16 between
#                   bf16 operands read three times what logits of 8 read);
#                   float8 3.9e-3 = 8.9 x the sound maximum; no yarn 2.5e-3; a
#                   window one key short 4.8e-3, 5.3e-3; the others >= 1.7e-2.
#                   The limit is 2.7 x the sound maximum (16 standard
#                   deviations above the mean), 2.1 x below no yarn's, 3.3 x
#                   below float8's.
#   delta_norm_gap  worst leaf. Sound 1.6e-4 .. 3.4e-4 (a router or an expert
#                   matrix); float8 7.0e-4 (not apart: Adam normalises the
#                   step); a window one key short 1.3e-3, 2.1e-3; no yarn
#                   2.9e-3; no window 5.4e-3; no factor 4.5e-2; no gate 0.13.
#                   Held against a step that returns its state unchanged (1.0)
#                   with the room above the reading: 3.0 x the sound maximum,
#                   1000 x below 1.
# ---------------------------------------------------------------------------
LIMITS = {"loss_gap": 2.0e-4, "grad_norm_gap": 3.0e-2, "grad_norm_gap_median": 1.2e-3,
          "delta_norm_gap": 1.0e-3}
COMPARISONS = common.WORST_LEAF + (("grad_norm_gap_median", "grad_norms", "median", None),)
CONTROL = "float8_e4m3fn"
#: the structural controls: each must fail the limits
CONTROLS = ("drop_window", "window_511", "drop_yarn", "drop_rope_scale", "drop_gate")
QUERY_BLOCK = 1024       # queries whose [block, t] scores exist at a time
LOSS_ROWS = 2048         # tokens whose logits exist at a time
F32 = jnp.float32


def layers(cfg: dict):
    """(is the layer windowed, query heads, "dense" | "moe") of each layer
    built: the published layers `layers_first` .. on."""
    first = cfg.get("layers_first", 0)
    kinds = {"full_attention": False, "sliding_attention": True}
    return [(kinds[cfg["layer_types"][i]], cfg["num_attention_heads_per_layer"][i],
             "dense" if i in cfg["mlp_only_layers"] else "moe")
            for i in range(first, first + cfg["num_hidden_layers"])]


def recipe(cfg: dict, windowed: bool) -> dict:
    return cfg["rope_parameters"]["sliding_attention" if windowed else "full_attention"]


def leaf_shapes(cfg: dict) -> dict:
    d, v, hd, kv = (cfg[k] for k in ("hidden_size", "vocab_size", "head_dim",
                                     "num_key_value_heads"))
    e, e_all = cfg["num_experts"], cfg["num_experts_published"]
    f, s = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    shapes = {"embed": (v, d)}
    for i, (_, h, ffn_kind) in enumerate(layers(cfg)):
        p = f"l{i}."
        shapes.update({p + "norm1": (d,), p + "attn.wqkv": (d, (h + 2 * kv) * hd),
                       p + "attn.wg": (d, h), p + "attn.wo": (h * hd, d), p + "norm2": (d,)})
        if ffn_kind == "dense":
            shapes.update({p + "mlp.wgu": (d, 2 * cfg["intermediate_size"]),
                           p + "mlp.wd": (cfg["intermediate_size"], d)})
        else:
            shapes.update({
                p + "moe.router": (d, e_all),
                p + "moe.wgu": (e, d, 2 * f), p + "moe.wd": (e, f, d),
                p + "moe.shared_wgu": (d, 2 * s), p + "moe.shared_wd": (s, d)})
    shapes["final_norm"] = (d,)
    shapes["head"] = (d, v)
    return shapes


#: reference leaf of a layer -> (which of the layer's two blocks, the leaf
#: inside `SubLayerBlock`'s params)
_BLOCK_LEAF = {
    "norm1": (0, "norm", "w"), "norm2": (1, "norm", "w"),
    "attn.wqkv": (0, "sub", "Wqkv"), "attn.wg": (0, "sub", "Wg"), "attn.wo": (0, "sub", "Wo"),
    "mlp.wgu": (1, "sub", "Wgu"), "mlp.wd": (1, "sub", "Wd"),
    "moe.router": (1, "sub", "router"), "moe.wgu": (1, "sub", "Wgu"),
    "moe.wd": (1, "sub", "Wd"), "moe.shared_wgu": (1, "sub", "shared_Wgu"),
    "moe.shared_wd": (1, "sub", "shared_Wd"),
}


def program_paths(cfg: dict) -> dict:
    """Reference leaf -> leaf of `MultiLayerNetwork.params`: layer_0 the
    embedding, layer_{1+2i} and layer_{2+2i} the attention's and the
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


#: what `init_params` adds to iid weights so that WHICH keys a query sees and
#: HOW FAST each pair turns show in the numbers the comparison reads (norms):
#: channel 0 of the hidden state is a CONSTANT (what a trained model's
#: massive-activation channels are): the embedding writes CHANNEL there for
#: every token, no matrix that writes to the residual stream touches it, and
#: no matrix reads it but the rotary columns of q and k in Wqkv
CHANNEL = 1.0
#: the logit a head gives the token `look_back(..)` positions before the query
#: from the constant channel alone: row 0 of a key head's rotary columns is
#: gain N(0, 1), of a query head's the same vector turned back that far, the
#: gain such that |u|^2 scale^2 / sqrt(head_dim) is PEAK. There is no q/k norm
#: to bound a logit, so the gain is what keeps the softmax from a one-hot:
#: against the ~1.5 of the iid part, 8 gives the token that far back about
#: two thirds of a windowed head's mass, 11 about half of a global head's
#: over 8192 keys
PEAK = {True: 8.0, False: 11.0}
#: an EDGE head — one whose distance is the window's last key — gets PEAK_EDGE
#: from the FAST_PAIRS fastest pairs alone: the key next to it gets two thirds of
#: that, the one after nothing, so that a window one key short leaves the head
#: without what it looked at (over all pairs the preference is a hump hundreds of
#: keys wide — the slow pairs hardly turn between neighbours — and one key fewer
#: moves no norm the comparison reads: my chip runs, PR 47)
PEAK_EDGE, FAST_PAIRS = 16.0, 4
#: tokens of the seeded sequence `init_params` measures the constant channel on
CALIBRATION_TOKENS = 256
#: leaves whose row 0 (they read the hidden state) / column 0 (they write it)
#: is zero at the start
READS = ("attn.wqkv", "attn.wg", "moe.router", "mlp.wgu", "moe.wgu", "moe.shared_wgu", "head")
WRITES = ("attn.wo", "mlp.wd", "moe.wd", "moe.shared_wd")


def look_back(cfg: dict, windowed: bool, head: int, n_heads: int) -> int:
    """The distance head `head` of `n_heads` prefers: in a sliding layer
    INSIDE the window — every other head (0, 2, ..) the window's LAST key,
    window - 1 back: one key fewer and half of the layer's heads lose what
    they look at —, the others spread from 1 to window - 2; in a global layer
    BEYOND the window, spread over a quarter of the sequence."""
    w = cfg["sliding_window"]
    if windowed:
        return w - 1 if head % 2 == 0 else 1 + head * (w - 3) // max(n_heads - 1, 1)
    return w + 1 + head * (cfg["input"]["seq_len"] // 4) // n_heads


def init_params(cfg: dict, seed: int) -> dict:
    """Seeded weights in one jitted call. Matrices N(0, 0.02); embedding rows
    N(0, 1); norm weights 1 + N(0, 0.02) (not exactly 1, so that a leaf
    installed in the wrong place shows); the head gates' Wg N(0, 0.5 /
    sqrt(d)), so that the gates differ by head and token and are far from 0
    and 1. ONE thing is not iid, because with iid weights over iid token ids
    every statistic of a step is the same whatever the rotation does and
    whichever keys a query sees (the scores are exchangeable over positions:
    PERF.md section 6, PR 38): the constant channel, read by the rotary
    columns of q and k (head h then prefers the token `look_back` before it;
    without the rotation, with other frequencies, or with that key outside the
    window, the preference is gone)."""
    shapes = leaf_shapes(cfg)
    d, hd, kv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    built = layers(cfg)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            normal = jax.random.normal(jax.random.fold_in(key, i), shape, F32)
            if name.endswith(("norm", "norm1", "norm2")):
                out[name] = 1.0 + 0.02 * normal
            elif name == "embed":
                out[name] = normal.at[:, 0].set(CHANNEL)
            elif name.endswith("attn.wg"):
                out[name] = (0.5 / math.sqrt(d) * normal).at[0, :].set(0.0)
            elif name.endswith(READS):
                out[name] = (0.02 * normal).at[..., 0, :].set(0.0)
            elif name.endswith(WRITES):
                out[name] = (0.02 * normal).at[..., 0].set(0.0)
            else:
                out[name] = 0.02 * normal
        # the constant channel's rows, a layer at a time: the residual stream grows
        # (the dense feed-forward alone doubles its variance), the pre-norm divides
        # the constant by that, and with no q/k norm a head's logit falls with its
        # SQUARE — at the cell's size the preferred key of a layer-1 head got 3 of
        # the 8 meant and every structural control but the largest passed (my chip
        # runs, PR 47). So the gain of layer i is set against what the constant
        # channel IS behind layer i's pre-norm, measured on one seeded sequence
        # through the layers before it
        ids = jax.random.randint(jax.random.fold_in(key, 2 * len(shapes)),
                                 (min(cfg["input"]["seq_len"], CALIBRATION_TOKENS),), 0,
                                 cfg["vocab_size"])
        x = out["embed"][ids]
        for i, (windowed, h, _) in enumerate(built):
            rec = recipe(cfg, windowed)
            rot = int(hd * rec.get("partial_rotary_factor", 1.0))
            _, scale = frequencies(rot, rec)
            channel = jnp.mean(jnp.abs(rms(x, out[f"l{i}.norm1"], cfg["rms_norm_eps"])[:, 0]))
            gain = math.sqrt(PEAK[windowed] * math.sqrt(hd) / (rot * scale * scale)) / channel
            fresh = jax.random.fold_in(key, len(shapes) + i)
            # a key head's vector: +- gain a feature, the FAST pairs (which turn a
            # radian or so a token: what tells a key from its neighbour) raised so
            # that they alone give an edge head's logit
            pairs = rot // 2
            fast = min(FAST_PAIRS, pairs // 2) if windowed else 0
            is_fast = (jnp.arange(rot) % pairs) < fast
            raised = math.sqrt(PEAK_EDGE / PEAK[True] * pairs / max(fast, 1))
            sign = jnp.where(jax.random.bernoulli(fresh, 0.5, (kv, rot)), 1.0, -1.0)
            u = gain * sign * jnp.where(is_fast, raised, 1.0)                 # [kv, rot]
            # a query head reads its key head's vector turned back by its distance
            # (the product of the two turned vectors peaks where the key is that far
            # before the query): an edge head the fast pairs alone — a sharp peak on
            # ONE key —, every other head the rest — a broad one around its distance.
            # The tables' scale belongs to the step, not to the weights
            far = [look_back(cfg, windowed, j, h) for j in range(h)]
            edge = jnp.asarray([windowed and d == cfg["sliding_window"] - 1 for d in far])
            mine = jnp.where(edge[:, None] == is_fast[None, :], jnp.repeat(u, h // kv, axis=0), 0.0)
            pad = ((0, 0), (0, hd - rot))
            turned = rotate(jnp.pad(mine, pad), rec, -jnp.asarray(far, F32)) / scale   # [h, hd]
            u = jnp.pad(u, pad)
            row = jnp.concatenate([turned.reshape(-1), u.reshape(-1), jnp.zeros((kv * hd,), F32)])
            out[f"l{i}.attn.wqkv"] = out[f"l{i}.attn.wqkv"].at[0].set(row)
            if i + 1 < len(built):
                x = block(out, x, cfg, i)
        return out

    return jax.jit(make)(common.seed_key(seed))


def init_state(cfg: dict, seed: int) -> dict:
    return {}


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------
def rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def frequencies(rot: int, rec: dict, yarn: bool = True):
    """(f_j for j = 0 .. rot/2 - 1, the factor on cos and sin) of a rotary
    recipe, the yarn schedule written out from its five published numbers."""
    theta = float(rec["rope_theta"])
    j = jnp.arange(rot // 2, dtype=F32)
    e = theta ** (-2.0 * j / rot)
    if rec.get("rope_type", "default") == "default":
        return e, 1.0
    if rec["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rec['rope_type']!r}")
    scale = float(rec["attention_factor"])
    if not yarn:
        return e, scale

    def c(n):
        return (rot * math.log(rec["original_max_position_embeddings"] / (2 * math.pi * n))
                / (2 * math.log(theta)))

    lo, hi = max(math.floor(c(rec["beta_fast"])), 0), min(math.ceil(c(rec["beta_slow"])), rot - 1)
    r = jnp.clip((j - lo) / (hi - lo), 0.0, 1.0)
    return e * (1.0 - r) + e / rec["factor"] * r, scale


def rotate(a, rec: dict, pos=None, yarn: bool = True, scaled: bool = True):
    """a [t, ..., hd], token p at position p (or `pos[p]`): the first R
    features of the last axis turn, pair j = features (j, j + R/2) by p f_j,
    cos and sin times the recipe's factor; the rest passes through."""
    t, hd = a.shape[0], a.shape[-1]
    rot = int(hd * rec.get("partial_rotary_factor", 1.0))
    freq, scale = frequencies(rot, rec, yarn)
    pos = jnp.arange(t, dtype=F32) if pos is None else pos
    ang = pos.reshape((t,) + (1,) * (a.ndim - 1)) * freq
    cos, sin = (f(ang) * (scale if scaled else 1.0) for f in (jnp.cos, jnp.sin))
    x, y = a[..., :rot // 2], a[..., rot // 2:rot]
    return jnp.concatenate([x * cos - y * sin, x * sin + y * cos, a[..., rot:]], axis=-1)


def attention(p, x, cfg, mm, windowed: bool, operand=None):
    """x [t, d] of one sequence -> [t, d] over the heads `p` holds (the
    shapes say how many): a head and a block of queries at a time (a scan, so
    that no two blocks' scores are alive together)."""
    hd = cfg["head_dim"]
    t, h = x.shape[0], p["wg"].shape[1]
    kv = (p["wqkv"].shape[1] // hd - h) // 2
    q, k, v = jnp.split(mm(x, p["wqkv"]), [h * hd, (h + kv) * hd], axis=-1)
    rec = recipe(cfg, windowed)
    turn = dict(yarn=operand != "drop_yarn", scaled=operand != "drop_rope_scale")
    q, k = rotate(q.reshape(t, h, hd), rec, **turn), rotate(k.reshape(t, kv, hd), rec, **turn)
    k, v = (jnp.repeat(a, h // kv, axis=1) for a in (k, v.reshape(t, kv, hd)))
    window = cfg["sliding_window"] if windowed and operand != "drop_window" else t
    if operand == "window_511":
        window = window - 1 if windowed else window
    qb = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    pos = jnp.arange(t)

    @jax.checkpoint
    def block(qh, rows, kh, vh):
        sc = mm(qh, kh.T) * hd ** -0.5
        back = rows[:, None] - pos[None, :]
        sc = jnp.where((back >= 0) & (back < window), sc, -jnp.inf)
        return mm(jax.nn.softmax(sc, axis=-1), vh)

    def head(a):
        qh, kh, vh = a
        o = lax.map(lambda b: block(b[0], b[1], kh, vh),
                    (qh.reshape(t // qb, qb, -1), pos.reshape(t // qb, qb)))
        return o.reshape(t, hd)

    o = jnp.moveaxis(lax.map(head, tuple(jnp.moveaxis(m, 1, 0) for m in (q, k, v))), 0, 1)
    if operand != "drop_gate":
        o = o * jax.nn.sigmoid(mm(x, p["wg"]))[..., None]
    return mm(o.reshape(t, h * hd), p["wo"])


def swiglu(x, wgu, wd, mm):
    gate, up = jnp.split(mm(x, wgu), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, wd)


def route(p, x, cfg, mm):
    """x [n, d] -> weights [n, experts]: zero but at the chosen."""
    s = jax.nn.softmax(mm(x, p["router"]), axis=-1)
    chosen = s >= lax.top_k(s, cfg["num_experts_per_tok"])[0][:, -1:]   # a dense 0/1 mask
    w = jnp.where(chosen, s, 0.0)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return w * cfg["moe_routed_scaling_factor"]


def moe(p, x, cfg, mm, held=None, shared: bool = True):
    """x [n, d] -> [n, d]: the terms of the experts held (`held` =
    (first, count), default the configuration's share) and, with `shared`,
    the shared expert. Every held expert is computed on every token and
    weighted by its (possibly zero) routing weight: plain, not fast."""
    first, count = held if held else (cfg.get("experts_first", 0), cfg["num_experts"])
    w = route(p, x, cfg, mm)[:, first:first + count]

    def one(acc, e):
        wgu, wd, wt = e
        term = jax.checkpoint(
            lambda x_, a, b, w_: w_[:, None] * swiglu(x_, a, b, mm))(x, wgu, wd, wt)
        return acc + term, None

    out, _ = lax.scan(one, jnp.zeros_like(x), (p["wgu"], p["wd"], w.T))
    return out + swiglu(x, p["shared_wgu"], p["shared_wd"], mm) if shared else out


def _sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def _mm(operand):
    return common.matmul(operand if operand == CONTROL else None)


def mixer(params, x, cfg, i, operand=None):
    """a = x + attn(rms(x)) of layer i on one sequence x [t, d]."""
    p = _sub(params, f"l{i}.")
    a = rms(x, p["norm1"], cfg["rms_norm_eps"])
    return x + attention(_sub(p, "attn."), a, cfg, _mm(operand), layers(cfg)[i][0], operand)


def ffn(params, h, cfg, i, operand=None):
    """y = h + ffn(rms(h)) of layer i."""
    mm = _mm(operand)
    p = _sub(params, f"l{i}.")
    a = rms(h, p["norm2"], cfg["rms_norm_eps"])
    if layers(cfg)[i][2] == "dense":
        return h + swiglu(a, p["mlp.wgu"], p["mlp.wd"], mm)
    return h + moe(_sub(p, "moe."), a, cfg, mm)


def block(params, x, cfg, i, operand=None):
    """One layer on one sequence x [t, d]; each half is one checkpoint."""
    h = jax.checkpoint(lambda p, x_: mixer(p, x_, cfg, i, operand))(params, x)
    return jax.checkpoint(lambda p, h_: ffn(p, h_, cfg, i, operand))(params, h)


def hidden(params, row, cfg, operand=None):
    """[t] int32 ids of one sequence -> [t, d] after the final norm."""
    x = params["embed"][row]
    for i in range(cfg["num_hidden_layers"]):
        x = block(params, x, cfg, i, operand)
    return rms(x, params["final_norm"], cfg["rms_norm_eps"])


def row_loss(params, row, labels, cfg, operand=None):
    """Sum of next-token cross-entropies of one sequence, the head and the
    log-softmax LOSS_ROWS tokens at a time (`tie_word_embeddings` false: the
    head is a matrix of its own)."""
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
# the reference's steps, lean: 672 M float32 parameters with their gradient
# and Adam's two moments are 10.8 GB of the chip's 16, so the starting weights
# stay on the host and Adam runs leaf by leaf
# ---------------------------------------------------------------------------
def _adam_leaf(args: dict):
    """DL4J's AdamUpdater on one leaf (bias correction folded into the step
    size, epsilon added to sqrt(v)), every array float32 whatever
    `jax_enable_x64` says (`tests/` switches it on)."""
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
    chip once but not five times: a row's gradient comes from one call and
    the rows' are added leaf by leaf, Adam's two moments wait on the HOST
    between steps and visit the chip one leaf at a time. `params0`: host
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
