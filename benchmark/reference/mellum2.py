"""Plain reference for `mellum` configurations (`mellum2-12b-a2.5b-l4`).

`mellum` (`JetBrains/Mellum2-12B-A2.5B-Instruct`, `config.json`): a decoder of
pre-norm layers with two residuals each, `a = x + attn(rms(x))`,
`y = a + moe(rms(a))`. Published layer l (from 0) is `layer_types[l]`:
"sliding_attention" — a query sees the `sliding_window` keys up to and with
its own — or "full_attention", the whole past; every layer has
`num_attention_heads` query heads over `num_key_value_heads` key/value heads
of `head_dim` and the rotary recipe of its type; every feed-forward is
`num_experts` softmax-routed swiglu experts, no shared one, no dense layer.
Written in float32 `jax.numpy` at matmul precision "highest" from the layer
equations of ISSUE 52; it imports nothing of `deeplearning4j_tpu` and takes no
array the program made.

  norm      rms(x; w) = x rsqrt(mean x^2 + eps) w         (plain weight, from 1)
  attention [q | k | v] = x Wqkv: H query heads, KV key/value heads of 128; no
            q/k norm, no gate, no bias; positions over ALL 128 features of
            every q and k head (no partial_rotary_factor), pair j = features
            (j, j + 64), position = the token's index from 0:
              sliding  angle p theta^(-2j/128)
              full     yarn: e_j = theta^(-2j/128); c(n) = 128 ln(original /
                       (2 pi n)) / (2 ln theta); lo = floor(c(beta_fast)), hi =
                       ceil(c(beta_slow)); r_j = clip((j - lo) / (hi - lo), 0,
                       1); f_j = e_j (1 - r_j) + (e_j / factor) r_j; angle
                       p f_j; cos and sin TIMES attention_factor
            head h reads key/value head h // (H / KV); scores q . k / sqrt(128);
            full: j <= i; sliding: i - window < j <= i (`window` keys, the
            query's own among them); softmax materialised in query blocks;
            out = concat_heads(o_h) Wo
  experts   p = softmax(u Wr) over ALL experts; the top-k; weights = p at the
            chosen / their sum (norm_topk_prob), no scaling factor;
            out = sum over the chosen experts of w_e expert_e(u), swiglus of
            896 — the UNCUT layer: all 64 experts, every token's whole top-8.
            The choice is a dense 0/1 mask over the experts and every expert
            is computed on every token: no sort, no buffer, no exchange, no
            drop.

Flat layouts where the published checkpoint has separate matrices, each a
relabelling: [gate | up], [q | k | v].

The layout (not mathematics): 1.78 B float32 parameters with their gradient
are 14 GB, so where the process has the `expert_parallel` devices of the
deployment (`ranks`), `init_params` and `train_steps` lay every expert
matrix [64, ..] over them as 4 x [16, ..] and everything else on each; the
experts are then walked as 16 steps of one expert a device (`moe`), every
device on all the tokens of the sequence, which is what the arrays' layout
makes of the same sum. On one device (the CPU tests) the same code runs with
no layout.

Controls (the `operand` argument), each a whole reference: "float8_e4m3fn"
rounds the operands of every product; the faults of the new mechanics and of
the mathematics the model shares with Laguna: "drop_rank_back" (what the experts
of ONE expert-parallel rank — rank 1, experts 16 .. 31 — return is left out of
every token's sum: an exchange that loses a rank's rows), "window_short" (one
key fewer), "drop_yarn" (the full layers' frequencies plain theta^(-2j/128),
the factor kept).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark.reference import common

# ---------------------------------------------------------------------------
# Limits of the comparison, each set from readings on the four-chip v5e host at
# the cell's own size (4 x 8192 tokens, published widths; my chip runs, PR 52:
# SOUND = the [check] lines of the runs PERF.md section 2 lists; the controls
# `benchmark/tests/read_limits_mesh.py`, each a whole reference). The model is
# Laguna's attention (the same constant-channel seeding, edge heads at a logit
# of 16) over experts that are all present, and its sound readings fall inside
# Laguna's ranges, so the four limits are `reference/laguna.py`'s:
#   loss_gap        sound 2.4e-6 .. 3.2e-5 (15 step losses of five seeds); float8
#                   4.0e-6, one rank's returned rows left out 9.7e-5: the loss
#                   at seeded weights hardly moves. The accepted cells' limit
#                   leaves the readings 6.2 x.
#   grad_norm_gap   worst leaf, against gross faults. Sound 4.2e-3 .. 1.09e-2
#                   (four of five `l0.norm1`: a pre-norm's weight gradient has
#                   one large component, the constant channel's); float8 2.9e-2
#                   on the same leaf: THIS NUMBER DOES NOT PART THE PRECISIONS,
#                   the median below does. One rank's returned rows left out
#                   0.140 (`l1.moe.wd`). The limit is 2.75 x the sound maximum
#                   and 4.7 x below the lost rank's.
#   grad_norm_gap_median  the MEDIAN leaf: the number the lower precision
#                   fails. Sound 1.0e-4 .. 6.1e-4 (sorted: 1.0, 3.0, 3.1, 4.0,
#                   6.1 e-4); float8 4.0e-3 = 6.5 x the sound maximum; the lost
#                   rank 6.5e-3. The limit is 1.96 x the sound maximum (thin:
#                   PERF.md section 7) and 3.3 x below float8's.
#   delta_norm_gap  worst leaf. Sound 1.5e-4 .. 2.3e-4 (an attention or an
#                   expert matrix); after ONE step float8 1.09e-3 (not apart:
#                   Adam normalises the step), the lost rank 0.135. Held against
#                   a step that returns its state unchanged (1.0) with the room
#                   above the reading: 4.3 x the sound maximum, 1000 x below 1.
# The controls were read after one step (`read_limits_mesh.py --steps 1`, seed
# 2147520031): each a whole reference; five sound seeds and one control seed
# are what 150 chip-minutes held on a host of four.
# ---------------------------------------------------------------------------
LIMITS = {"loss_gap": 2.0e-4, "grad_norm_gap": 3.0e-2, "grad_norm_gap_median": 1.2e-3,
          "delta_norm_gap": 1.0e-3}
COMPARISONS = common.WORST_LEAF + (("grad_norm_gap_median", "grad_norms", "median", None),)
CONTROL = "float8_e4m3fn"
#: the structural controls: each must fail the limits
CONTROLS = ("drop_rank_back", "window_short", "drop_yarn")
DROPPED_RANK = 1         # "drop_rank_back": whose returned rows are lost
QUERY_BLOCK = 1024       # queries whose [block, t] scores exist at a time
LOSS_ROWS = 2048         # tokens whose logits exist at a time
F32 = jnp.float32


def layers(cfg: dict):
    """Is each layer built windowed: the published layers `layers_first` .. on."""
    first = cfg.get("layers_first", 0)
    return [cfg["layer_types"][i] == "sliding_attention"
            for i in range(first, first + cfg["num_hidden_layers"])]


def recipe(cfg: dict, windowed: bool) -> dict:
    return cfg["rope_parameters"]["sliding_attention" if windowed else "full_attention"]


def leaf_shapes(cfg: dict) -> dict:
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    shapes = {"embed": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        shapes.update({p + "norm1": (d,), p + "attn.wqkv": (d, (h + 2 * kv) * hd),
                       p + "attn.wo": (h * hd, d), p + "norm2": (d,),
                       p + "moe.router": (d, e),
                       p + "moe.wgu": (e, d, 2 * f), p + "moe.wd": (e, f, d)})
    shapes["final_norm"] = (d,)
    shapes["head"] = (d, v)
    return shapes


#: reference leaf of a layer -> (which of the layer's two blocks, the leaf
#: inside `SubLayerBlock`'s params)
_BLOCK_LEAF = {
    "norm1": (0, "norm", "w"), "norm2": (1, "norm", "w"),
    "attn.wqkv": (0, "sub", "Wqkv"), "attn.wo": (0, "sub", "Wo"),
    "moe.router": (1, "sub", "router"), "moe.wgu": (1, "sub", "Wgu"),
    "moe.wd": (1, "sub", "Wd"),
}


def program_paths(cfg: dict) -> dict:
    """Reference leaf -> leaf of `MultiLayerNetwork.params`: layer_0 the
    embedding, layer_{1+2i} and layer_{2+2i} the attention's and the experts'
    block of layer i, then the final norm and the head."""
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


# ---------------------------------------------------------------------------
# the layout
# ---------------------------------------------------------------------------
def ranks(cfg: dict) -> int:
    """The devices the expert matrices are laid over: the deployment's
    `expert_parallel` where this process has that many, else 1."""
    want = int(cfg.get("expert_parallel", 1))
    return want if len(jax.devices()) >= want else 1


def layout(cfg: dict) -> dict:
    """Leaf -> its sharding: an expert matrix split on its expert dimension
    over `ranks` devices, every other leaf whole on each; {} on one device."""
    n = ranks(cfg)
    if n == 1:
        return {}
    mesh = Mesh(np.array(jax.devices()[:n]), ("ranks",))
    return {name: NamedSharding(mesh, P("ranks") if name.endswith(("moe.wgu", "moe.wd"))
                                else P()) for name in leaf_shapes(cfg)}


#: what `init_params` adds to iid weights so that WHICH keys a query sees and
#: HOW FAST each pair turns show in the numbers the comparison reads (norms),
#: as `reference/laguna.py` does: channel 0 of the hidden state is a CONSTANT:
#: the embedding writes CHANNEL there for every token, no matrix that writes to
#: the residual stream touches it, and no matrix reads it but the rotary
#: columns of q and k in Wqkv
CHANNEL = 1.0
#: the logit a head gives the token `look_back(..)` positions before the query
#: from the constant channel alone (a sliding layer's heads, a full layer's)
PEAK = {True: 8.0, False: 11.0}
#: an EDGE head — one whose distance is the window's last key — gets PEAK_EDGE
#: from the FAST_PAIRS fastest pairs alone: a sharp preference for ONE key, so
#: that a window one key short leaves the head without what it looked at
PEAK_EDGE, FAST_PAIRS = 16.0, 4
#: tokens of the seeded sequence `init_params` measures the constant channel on
CALIBRATION_TOKENS = 256
#: leaves whose row 0 (they read the hidden state) / column 0 (they write it)
#: is zero at the start
READS = ("attn.wqkv", "moe.router", "moe.wgu", "head")
WRITES = ("attn.wo", "moe.wd")


def look_back(cfg: dict, windowed: bool, head: int, n_heads: int) -> int:
    """The distance head `head` of `n_heads` prefers: in a sliding layer
    INSIDE the window — every other head (0, 2, ..) the window's LAST key,
    window - 1 back —, the others spread from 1 to window - 2; in a full layer
    BEYOND the window, spread over a quarter of the sequence."""
    w = cfg["sliding_window"]
    if windowed:
        return w - 1 if head % 2 == 0 else 1 + head * (w - 3) // max(n_heads - 1, 1)
    return w + 1 + head * (cfg["input"]["seq_len"] // 4) // n_heads


def init_params(cfg: dict, seed: int) -> dict:
    """Seeded weights in one jitted call, laid out by `layout`. Matrices
    N(0, 0.02); embedding rows N(0, 1); norm weights 1 + N(0, 0.02). ONE thing
    is not iid, because with iid weights over iid token ids every statistic of
    a step is the same whatever the rotation does and whichever keys a query
    sees (PERF.md section 6, PR 38): the constant channel, read by the rotary
    columns of q and k — head h then prefers the token `look_back` before it;
    each layer's gain is set against what the constant channel IS behind that
    layer's pre-norm, measured on one seeded sequence through the layers
    before it (there is no q/k norm to bound a logit)."""
    shapes = leaf_shapes(cfg)
    hd, h, kv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    built = layers(cfg)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            normal = jax.random.normal(jax.random.fold_in(key, i), shape, F32)
            if name.endswith(("norm", "norm1", "norm2")):
                out[name] = 1.0 + 0.02 * normal
            elif name == "embed":
                out[name] = normal.at[:, 0].set(CHANNEL)
            elif name.endswith(READS):
                out[name] = (0.02 * normal).at[..., 0, :].set(0.0)
            elif name.endswith(WRITES):
                out[name] = (0.02 * normal).at[..., 0].set(0.0)
            else:
                out[name] = 0.02 * normal
        ids = jax.random.randint(jax.random.fold_in(key, 2 * len(shapes)),
                                 (min(cfg["input"]["seq_len"], CALIBRATION_TOKENS),), 0,
                                 cfg["vocab_size"])
        x = out["embed"][ids]
        for i, windowed in enumerate(built):
            rec = recipe(cfg, windowed)
            _, scale = frequencies(hd, rec)
            channel = jnp.mean(jnp.abs(rms(x, out[f"l{i}.norm1"], cfg["rms_norm_eps"])[:, 0]))
            gain = math.sqrt(PEAK[windowed] * math.sqrt(hd) / (hd * scale * scale)) / channel
            fresh = jax.random.fold_in(key, len(shapes) + i)
            # a key head's vector: +- gain a feature, the FAST pairs (which turn a
            # radian or so a token: what tells a key from its neighbour) raised so
            # that they alone give an edge head's logit
            pairs = hd // 2
            fast = min(FAST_PAIRS, pairs // 2) if windowed else 0
            is_fast = (jnp.arange(hd) % pairs) < fast
            raised = math.sqrt(PEAK_EDGE / PEAK[True] * pairs / max(fast, 1))
            sign = jnp.where(jax.random.bernoulli(fresh, 0.5, (kv, hd)), 1.0, -1.0)
            u = gain * sign * jnp.where(is_fast, raised, 1.0)                 # [kv, hd]
            # a query head reads its key head's vector turned back by its distance:
            # an edge head the fast pairs alone, every other head the rest
            far = [look_back(cfg, windowed, j, h) for j in range(h)]
            edge = jnp.asarray([windowed and back == cfg["sliding_window"] - 1 for back in far])
            mine = jnp.where(edge[:, None] == is_fast[None, :], jnp.repeat(u, h // kv, axis=0), 0.0)
            turned = rotate(mine, rec, -jnp.asarray(far, F32)) / scale        # [h, hd]
            row = jnp.concatenate([turned.reshape(-1), u.reshape(-1), jnp.zeros((kv * hd,), F32)])
            out[f"l{i}.attn.wqkv"] = out[f"l{i}.attn.wqkv"].at[0].set(row)
            if i + 1 < len(built):
                x = block(out, x, cfg, i)
        return out

    return jax.jit(make, out_shardings=layout(cfg) or None)(common.seed_key(seed))


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


def rotate(a, rec: dict, pos=None, yarn: bool = True):
    """a [t, ..., hd], token p at position p (or `pos[p]`): pair j = features
    (j, j + hd/2) of the last axis turns by p f_j, cos and sin times the
    recipe's factor."""
    t, hd = a.shape[0], a.shape[-1]
    freq, scale = frequencies(hd, rec, yarn)
    pos = jnp.arange(t, dtype=F32) if pos is None else pos
    ang = pos.reshape((t,) + (1,) * (a.ndim - 1)) * freq
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x, y = a[..., :hd // 2], a[..., hd // 2:]
    return jnp.concatenate([x * cos - y * sin, x * sin + y * cos], axis=-1)


def attention(p, x, cfg, mm, windowed: bool, operand=None):
    """x [t, d] of one sequence -> [t, d]: a head and a block of queries at a
    time (a scan, so that no two blocks' scores are alive together)."""
    hd, h, kv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    t = x.shape[0]
    q, k, v = jnp.split(mm(x, p["wqkv"]), [h * hd, (h + kv) * hd], axis=-1)
    rec = recipe(cfg, windowed)
    yarn = operand != "drop_yarn"
    q, k = rotate(q.reshape(t, h, hd), rec, yarn=yarn), rotate(k.reshape(t, kv, hd), rec, yarn=yarn)
    k, v = (jnp.repeat(a, h // kv, axis=1) for a in (k, v.reshape(t, kv, hd)))
    window = cfg["sliding_window"] if windowed else t
    if windowed and operand == "window_short":
        window -= 1
    qb = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    pos = jnp.arange(t)

    @jax.checkpoint
    def scores(qh, rows, kh, vh):
        sc = mm(qh, kh.T) * hd ** -0.5
        back = rows[:, None] - pos[None, :]
        sc = jnp.where((back >= 0) & (back < window), sc, -jnp.inf)
        return mm(jax.nn.softmax(sc, axis=-1), vh)

    def head(a):
        qh, kh, vh = a
        o = lax.map(lambda b: scores(b[0], b[1], kh, vh),
                    (qh.reshape(t // qb, qb, -1), pos.reshape(t // qb, qb)))
        return o.reshape(t, hd)

    o = jnp.moveaxis(lax.map(head, tuple(jnp.moveaxis(m, 1, 0) for m in (q, k, v))), 0, 1)
    return mm(o.reshape(t, h * hd), p["wo"])


def swiglu(x, wgu, wd, mm):
    gate, up = jnp.split(mm(x, wgu), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, wd)


def route(p, x, cfg, mm):
    """x [n, d] -> weights [n, experts]: zero but at the chosen."""
    s = jax.nn.softmax(mm(x, p["router"]), axis=-1)
    chosen = s >= lax.top_k(s, cfg["num_experts_per_tok"])[0][:, -1:]   # a dense 0/1 mask
    w = jnp.where(chosen, s, 0.0)
    return w / w.sum(-1, keepdims=True) if cfg["norm_topk_prob"] else w


def moe(p, x, cfg, mm, operand=None):
    """x [n, d] -> [n, d]: every expert on every token, weighted by its
    (mostly zero) routing weight: plain, not fast. The experts are walked in
    `groups` of e / groups — experts g x e/groups + j for every g at step j, the
    expert matrices' own layout where they are laid over devices — and the
    groups' sums added at the end; `groups` is the deployment's count of
    expert-parallel ranks whatever the devices, so that "drop_rank_back"
    names the same experts everywhere."""
    n, d = x.shape
    groups = int(cfg.get("expert_parallel", 1))
    each = cfg["num_experts"] // groups
    w = route(p, x, cfg, mm).T.reshape(groups, each, n)
    wgu = p["wgu"].reshape(groups, each, d, -1)
    wd = p["wd"].reshape(groups, each, -1, d)
    term = jax.checkpoint(lambda x_, a, b, w_, j: w_[:, j, :, None] * swiglu(
        x_, a[:, j], b[:, j], mm))

    def one(acc, j):
        return acc + term(x, wgu, wd, w, j), None

    by_group, _ = lax.scan(one, jnp.zeros((groups, n, d), F32), jnp.arange(each))
    if operand == "drop_rank_back":
        by_group = by_group.at[DROPPED_RANK].set(0.0)
    return by_group.sum(axis=0)


def _sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def _mm(operand):
    return common.matmul(operand if operand == CONTROL else None)


def mixer(params, x, cfg, i, operand=None):
    """a = x + attn(rms(x)) of layer i on one sequence x [t, d]."""
    p = _sub(params, f"l{i}.")
    a = rms(x, p["norm1"], cfg["rms_norm_eps"])
    return x + attention(_sub(p, "attn."), a, cfg, _mm(operand), layers(cfg)[i], operand)


def ffn(params, h, cfg, i, operand=None):
    """y = h + moe(rms(h)) of layer i."""
    p = _sub(params, f"l{i}.")
    a = rms(h, p["norm2"], cfg["rms_norm_eps"])
    return h + moe(_sub(p, "moe."), a, cfg, _mm(operand), operand)


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
    is one checkpoint and the rows are a scan."""
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
# the reference's steps, lean: the starting weights stay on the host, the
# working copy and its gradient lie over the devices by `layout`, and Adam's
# two moments wait on the host between steps and visit the devices a leaf at a
# time
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
    """`common.train_steps` with the same result. `params0`: host (numpy)
    arrays; a sequence's gradient comes from one call and the sequences' are
    added leaf by leaf."""
    where = layout(cfg)

    def put(name, a):
        a = np.asarray(a, np.float32)
        return jax.device_put(a, where[name]) if where else jnp.asarray(a)

    def grad(params, x, y):
        def f(p):
            with jax.default_matmul_precision("highest"):
                return row_loss(p, x, y, cfg, operand)
        return jax.value_and_grad(f)(params)

    grad = jax.jit(grad)
    add = jax.jit(jnp.add, donate_argnums=0)
    adam = _adam_leaf(cfg["optimizer"]["args"])
    params = {k: put(k, v) for k, v in params0.items()}
    m_host, v_host = {}, {}                             # Adam's moments, between steps
    losses, grad_norms = [], {}
    norm = lambda a: float(jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)))))  # noqa: E731
    for i, (x, y) in enumerate(batches):
        total, grads = 0.0, None
        for row, labels in zip(np.asarray(x), np.asarray(y)):
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
                m, v = put(k, m_host.pop(k)), put(k, v_host.pop(k))
            params[k], m, v = adam(params[k], g, m, v, i + 1)
            if i + 1 < len(batches):
                m_host[k], v_host[k] = jax.device_get((m, v))
    delta_norms = {k: norm(params[k] - put(k, params0[k])) for k in params}
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta_norms}
