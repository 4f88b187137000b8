"""Plain reference for `deepseek_v3` configurations (`kanana-2-30b-a3b-l5`).

`deepseek_v3` (`kakaocorp/kanana-2-30b-a3b-instruct-2601`, `config.json`): a
decoder of pre-norm layers with two residuals each, `h = x + mla(rms(x))`,
`y = h + ffn(rms(h))`. EVERY layer's mixer is latent attention (MLA) whose
positions are decoupled from its content: a rotary embedding on a
`qk_rope_head_dim`-wide part of each query head and on the ONE key part all
heads share, nothing else in the stack knows the order of the tokens. The
feed-forward is a dense swiglu for layer i <= `first_k_dense_replace` (from
1), else 128 sigmoid-routed swiglu experts, six a token, beside two ungated
shared experts. Written in float32 `jax.numpy` at matmul precision "highest"
from the layer equations of ISSUE 38; it imports nothing of
`deeplearning4j_tpu` and takes no array the program made.

  norm      rms(x; w) = x rsqrt(mean x^2 + eps) w         (plain weight, from 1)
  MLA       q = x Wq, 32 heads of [q_nope 128 | q_rope 64] (q_lora_rank null:
            no bottleneck); [c | kr] = x Wkva (512, 64); [k_nope | v] =
            rms(c; w_c) Wkvb, 32 heads of [128 | 128]; q_rope a head and kr
            ONCE are rotated: PAIR j = features (2j, 2j + 1) of the part
            (`rope_interleave`), by the angle p theta^(-2j / 64) at position
            p = the token's index, (a, b) -> (a cos - b sin, a sin + b cos);
            a head's key is [k_nope | kr], kr the same for every head;
            causal softmax at 192^-0.5 (rope_scaling null: no extra scale),
            materialised, in query blocks; out = concat_heads(o) Wo
  dense     (silu(x Wg) (x Wu)) Wd, [gate | up] one matrix of 2 x 6144
  experts   s = sigmoid(u Wr) over all 128; CHOSEN: the 6 largest of s +
            e_score_correction_bias (n_group 1, topk_group 1: no group limit;
            the bias chooses, it does not weigh); weights = s at the chosen /
            (their sum + 1e-20) x 2.448; expert e the swiglu of 768;
            out = sum over the chosen experts HELD HERE of w_e expert_e(u) +
            shared(u), the two shared experts one ungated swiglu of 2 x 768.
            The choice is a dense 0/1 mask over the 128: no sort, no buffer.

The flat layout [gate | up] is a concatenation where the published checkpoint
has separate matrices: a relabelling. Wq, Wkva, Wkvb, Wo are `q_proj`,
`kv_a_proj_with_mqa`, `kv_b_proj`, `o_proj` transposed, columns in the
published order (the rope columns interleaved as published).

The share (model-configs section 4): `n_routed_experts` of the file is the
count HELD by this rank (experts `experts_first` .. + count of the published
`n_routed_experts_published`); the router keeps its published width; what the
absent experts would add is left out, here and in the program alike.

Controls (the `operand` argument), each a whole reference: "float8_e4m3fn"
rounds the operands of every product; "drop_rope" rotates nothing (the fault
this configuration exists to catch); "half_split" pairs feature j with
j + 32 (the other convention, on columns in the published order);
"drop_expert" leaves the first held expert's terms out; "drop_shared" the
shared experts; "ignore_bias" chooses by the bare scores.
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
# own size (2 x 8192 tokens, published widths; my chip runs, PR 38: the
# program's gaps are the [check] lines of seven runs and
# benchmark/tests/read_leaf_gaps_ids.py on seeds 2147503821, 908, 2147513822,
# which reads every leaf: 10 seeds when these were set; the three controls
# the same script on each of those three seeds, each a whole reference;
# PERF.md section 2 has the table and the later runs):
#   loss_gap        sound 9.5e-8 .. 3.3e-5; float8 1.3e-5, 1.0e-4, 1.2e-4,
#                   no rotation <= 6.5e-5, the other pairing <= 8.7e-5: the
#                   loss at seeded weights hardly moves with precision OR
#                   with positions, so it takes the accepted cells' limit,
#                   which leaves the readings 6 x of room.
#   grad_norm_gap   worst leaf, against gross faults. Sound 1.7e-3 .. 2.7e-3
#                   on nine seeds (a latent projection of the first layers)
#                   and 2.04e-2 ONCE (seed 2147503904, `l0.norm1`, which
#                   reads 3.8e-5 .. 1.0e-3 on the three seeds read leaf by
#                   leaf); float8 3.8e-3 .. 6.1e-3: THIS NUMBER CANNOT PART THE
#                   PRECISIONS (as in kimi_linear.py), the median below does.
#                   No rotation 0.23 .. 0.37 (`l0.mla.wq`), the other pairing
#                   0.106 .. 0.187. The limit is 2.9 x the sound maximum,
#                   1.8 x below the other pairing's smallest and 3.9 x below
#                   no rotation's.
#   grad_norm_gap_median  the MEDIAN leaf: the number the lower precision
#                   fails. Sound 1.27e-4 .. 2.70e-4 (mean 1.96e-4, standard
#                   deviation 4.5e-5); float8 6.2e-4, 7.8e-4, 9.1e-4 — only
#                   2.3 x the sound maximum (Kimi-Linear's read 7.5 x); no
#                   rotation 2.0e-3 .. 3.1e-3, the other pairing 2.4e-3 ..
#                   2.8e-3. The limit is 1.67 x the sound maximum (5.6
#                   standard deviations above the mean); float8 fails it by
#                   1.4 x .. 2.0 x, the two rotation faults by 4.5 x and more.
#   delta_norm_gap  worst leaf. Sound 2.9e-4 .. 4.1e-4; float8 4.7e-4 ..
#                   6.6e-4 (not apart: Adam normalises the step); no rotation
#                   3.3e-2 .. 3.6e-2 (`mla.wq`), the other pairing 8.6e-3 ..
#                   2.0e-2. Held against a step that returns its state
#                   unchanged (1.0) with the room above the reading: 9.8 x
#                   the sound maximum, 2.2 x below the other pairing's
#                   smallest, 250 x below 1.
# ---------------------------------------------------------------------------
LIMITS = {"loss_gap": 2.0e-4, "grad_norm_gap": 6.0e-2, "grad_norm_gap_median": 4.5e-4,
          "delta_norm_gap": 4.0e-3}
COMPARISONS = common.WORST_LEAF + (("grad_norm_gap_median", "grad_norms", "median", None),)
CONTROL = "float8_e4m3fn"
QUERY_BLOCK = 1024       # queries whose [block, t] scores exist at a time
LOSS_ROWS = 2048         # tokens whose logits exist at a time
F32 = jnp.float32


def kinds(cfg: dict):
    """The feed-forward of each layer built: "dense" | "moe" (every mixer is
    latent attention)."""
    return ["dense" if i <= cfg["first_k_dense_replace"] else "moe"
            for i in range(1, cfg["num_hidden_layers"] + 1)]


def _dims(cfg):
    return dict(
        d=cfg["hidden_size"], v=cfg["vocab_size"],
        h=cfg["num_attention_heads"], rank=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
        ff=cfg["intermediate_size"],
        e=cfg["n_routed_experts"], e_all=cfg["n_routed_experts_published"],
        f=cfg["moe_intermediate_size"],
        fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"])


def leaf_shapes(cfg: dict) -> dict:
    s = _dims(cfg)
    d = s["d"]
    shapes = {"embed": (s["v"], d)}
    for i, ffn_kind in enumerate(kinds(cfg)):
        p = f"l{i}."
        shapes.update({
            p + "norm1": (d,),
            p + "mla.wq": (d, s["h"] * (s["nope"] + s["rope"])),
            p + "mla.wkva": (d, s["rank"] + s["rope"]), p + "mla.kv_norm": (s["rank"],),
            p + "mla.wkvb": (s["rank"], s["h"] * (s["nope"] + s["vd"])),
            p + "mla.wo": (s["h"] * s["vd"], d),
            p + "norm2": (d,)})
        if ffn_kind == "dense":
            shapes.update({p + "mlp.wgu": (d, 2 * s["ff"]), p + "mlp.wd": (s["ff"], d)})
        else:
            shapes.update({
                p + "moe.router": (d, s["e_all"]), p + "moe.select_bias": (s["e_all"],),
                p + "moe.wgu": (s["e"], d, 2 * s["f"]), p + "moe.wd": (s["e"], s["f"], d),
                p + "moe.shared_wgu": (d, 2 * s["fs"]), p + "moe.shared_wd": (s["fs"], d)})
    shapes["final_norm"] = (d,)
    shapes["head"] = (d, s["v"])
    return shapes


#: reference leaf of a layer -> (which of the layer's two blocks, the leaf
#: inside `SubLayerBlock`'s params)
_BLOCK_LEAF = {
    "norm1": (0, "norm", "w"), "norm2": (1, "norm", "w"),
    "mla.wq": (0, "sub", "Wq"), "mla.wkva": (0, "sub", "Wkva"),
    "mla.kv_norm": (0, "sub", "kv_norm"), "mla.wkvb": (0, "sub", "Wkvb"),
    "mla.wo": (0, "sub", "Wo"),
    "mlp.wgu": (1, "sub", "Wgu"), "mlp.wd": (1, "sub", "Wd"),
    "moe.router": (1, "sub", "router"), "moe.select_bias": (1, "sub", "select_bias"),
    "moe.wgu": (1, "sub", "Wgu"), "moe.wd": (1, "sub", "Wd"),
    "moe.shared_wgu": (1, "sub", "shared_Wgu"), "moe.shared_wd": (1, "sub", "shared_Wd"),
}


def program_paths(cfg: dict) -> dict:
    """Reference leaf -> leaf of `MultiLayerNetwork.params`: layer_0 the
    embedding, layer_{1+2i} and layer_{2+2i} the mixer's and the
    feed-forward's block of layer i, then the final norm and the head. No
    column is permuted: the program rotates the published pairs in place."""
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


#: what `init_params` adds to iid weights so that the ORDER of the tokens shows
#: in the numbers the comparison reads (norms). Channel 0 of the hidden state is
#: a CONSTANT (what a trained model's massive-activation channels are, and the
#: only way to a bias in a model that has none): the embedding writes CHANNEL
#: there for every token, no matrix that writes to the residual stream touches
#: it, and no matrix reads it but the rope columns of Wkva and Wq — so the
#: shared rope key part gets a token-independent vector `gain u` and head h's
#: rope query part the same vector turned back `look_back(h)` positions: the
#: token that far back gets ROPE_LOGIT more (at a hidden state of unit rms)
CHANNEL = 1.0
ROPE_LOGIT = 8.0
#: leaves whose row 0 (they read the hidden state) / column 0 (they write it)
#: is zero at the start
READS = ("mla.wq", "mla.wkva", "moe.router", "mlp.wgu", "moe.wgu", "moe.shared_wgu", "head")
WRITES = ("mla.wo", "mlp.wd", "moe.wd", "moe.shared_wd")


def rope_gain(cfg: dict) -> float:
    """g with (CHANNEL g)^2 E|u|^2 (qk_head_dim)^-0.5 = ROPE_LOGIT for
    u ~ N(0, 1) over the rope part: 1.32 at the published widths."""
    s = _dims(cfg)
    return math.sqrt(ROPE_LOGIT * (s["nope"] + s["rope"]) ** 0.5 / s["rope"]) / CHANNEL


def look_back(head: int) -> int:
    return 1 + 64 * head


def init_params(cfg: dict, seed: int) -> dict:
    """Seeded weights in one jitted call. Matrices N(0, 0.02); embedding rows
    N(0, 1) (the hidden state then has an rms near 1 in every layer, at
    every size, so ROPE_LOGIT means the same everywhere); norm weights
    1 + N(0, 0.02) (not exactly 1, so that a leaf installed in the wrong
    place shows); the selection bias N(0, 0.01), so that it changes some
    choices. One thing is NOT iid, because with iid weights over iid token
    ids every statistic of a step is the same whatever the rotation does
    (the scores are exchangeable over positions: a run without rotary, or
    with the wrong pairing, would pass any limit on norms): the constant
    channel above. A shared OFFSET on the embedding rows would do the same
    and was tried first (my chip run, PR 38): the averaging in attention
    keeps what tokens share and loses what tells them apart, every router
    then sees one hidden state, 1.3 % of the buffer filled where 12.5 % is
    expected, and a held expert's gradient is a few tokens' — gaps of 6 %
    in a sound run. The channel is read by the rope columns alone.

    With the rotation head h prefers the token look_back(h) before it —
    what a trained previous-token head is; without it the preference is
    gone (a constant), with the other pairing it is scrambled."""
    shapes = leaf_shapes(cfg)
    s = _dims(cfg)
    d, h, nope, r, rank = s["d"], s["h"], s["nope"], s["rope"], s["rank"]
    gain = rope_gain(cfg)
    turn = jnp.stack([rotate(jnp.eye(r, dtype=F32), float(cfg["rope_theta"]),
                             cfg["rope_interleave"], jnp.full((r,), -float(look_back(i)), F32))
                      for i in range(h)])                   # [h, r, r]: row e_i turned back

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            normal = jax.random.normal(jax.random.fold_in(key, i), shape, F32)
            if name.endswith(("norm", "norm1", "norm2")):
                out[name] = 1.0 + 0.02 * normal
            elif name.endswith("select_bias"):
                out[name] = 0.01 * normal
            elif name == "embed":
                out[name] = normal.at[:, 0].set(CHANNEL)
            elif name.endswith(READS):
                out[name] = (0.02 * normal).at[..., 0, :].set(0.0)
            elif name.endswith(WRITES):
                out[name] = (0.02 * normal).at[..., 0].set(0.0)
            else:
                out[name] = 0.02 * normal
        for i in range(cfg["num_hidden_layers"]):
            u = gain * jax.random.normal(jax.random.fold_in(key, len(shapes) + i), (r,), F32)
            out[f"l{i}.mla.wkva"] = out[f"l{i}.mla.wkva"].at[0, rank:].set(u)
            turned = jnp.einsum("r,hrc->hc", u, turn, precision=common.HIGHEST)
            wq = out[f"l{i}.mla.wq"].reshape(d, h, nope + r)
            out[f"l{i}.mla.wq"] = wq.at[0, :, nope:].set(turned).reshape(d, -1)
        return out

    return jax.jit(make)(common.seed_key(seed))


def init_state(cfg: dict, seed: int) -> dict:
    return {}


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------
def rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate(a, theta: float, interleave: bool = True, pos=None):
    """a [t, ..., r], token p at position p (or `pos[p]`): pair j of the last
    axis turns by p theta^(-2j / r). Interleaved (as published): pair j is
    features (2j, 2j + 1); half-split: (j, j + r/2)."""
    t, r = a.shape[0], a.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(r // 2, dtype=F32) / r)
    pos = jnp.arange(t, dtype=F32) if pos is None else pos
    ang = pos.reshape((t,) + (1,) * (a.ndim - 1)) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleave:
        pairs = a.reshape(a.shape[:-1] + (r // 2, 2))
        x, y = pairs[..., 0], pairs[..., 1]
        return jnp.stack([x * cos - y * sin, x * sin + y * cos], axis=-1).reshape(a.shape)
    x, y = a[..., :r // 2], a[..., r // 2:]
    return jnp.concatenate([x * cos - y * sin, x * sin + y * cos], axis=-1)


def mla(p, x, cfg, mm, rope="interleave"):
    """x [t, d] of one sequence -> [t, d]: a head and a block of queries at
    a time (a scan, so that no two blocks' scores are alive together).
    `rope`: "interleave" | "half_split" | None (no rotation), the pairing
    the configuration publishes unless a control says otherwise."""
    s = _dims(cfg)
    t, h, nope, vd = x.shape[0], s["h"], s["nope"], s["vd"]
    width = nope + s["rope"]
    q = mm(x, p["wq"]).reshape(t, h, width)
    c, kr = jnp.split(mm(x, p["wkva"]), [s["rank"]], axis=-1)
    kv = mm(rms(c, p["kv_norm"], cfg["rms_norm_eps"]), p["wkvb"]).reshape(t, h, nope + vd)
    q_rope = q[..., nope:]
    if rope is not None:
        theta = float(cfg["rope_theta"])
        q_rope = rotate(q_rope, theta, rope == "interleave")
        kr = rotate(kr, theta, rope == "interleave")          # once: one head for all
    q = jnp.concatenate([q[..., :nope], q_rope], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(kr[:, None, :], (t, h, s["rope"]))], -1)
    v = kv[..., nope:]
    qb = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    pos = jnp.arange(t)

    @jax.checkpoint
    def block(qh, rows, kh, vh):
        sc = mm(qh, kh.T) * width ** -0.5
        sc = jnp.where(rows[:, None] >= pos[None, :], sc, -jnp.inf)
        return mm(jax.nn.softmax(sc, axis=-1), vh)

    def head(a):
        qh, kh, vh = a
        o = lax.map(lambda b: block(b[0], b[1], kh, vh),
                    (qh.reshape(t // qb, qb, -1), pos.reshape(t // qb, qb)))
        return o.reshape(t, vd)

    o = lax.map(head, tuple(jnp.moveaxis(m, 1, 0) for m in (q, k, v)))
    return mm(jnp.moveaxis(o, 0, 1).reshape(t, h * vd), p["wo"])


def swiglu(x, wgu, wd, mm):
    gate, up = jnp.split(mm(x, wgu), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, wd)


def route(p, x, cfg, mm, ignore_bias=False):
    """x [n, d] -> weights [n, experts]: zero but at the chosen."""
    s = jax.nn.sigmoid(mm(x, p["router"]))
    sel = s if ignore_bias else s + p["select_bias"]
    chosen = sel >= lax.top_k(sel, cfg["num_experts_per_tok"])[0][:, -1:]   # a dense 0/1 mask
    w = jnp.where(chosen, s, 0.0)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"]


def moe(p, x, cfg, mm, held=None, skip=(), shared=True, ignore_bias=False):
    """x [n, d] -> [n, d]: the terms of the experts held (`held` =
    (first, count), default the configuration's share) plus the shared
    experts. Every held expert is computed on every token and weighted by
    its (possibly zero) routing weight: plain, not fast."""
    first, count = held if held else (cfg.get("experts_first", 0), cfg["n_routed_experts"])
    w = route(p, x, cfg, mm, ignore_bias)[:, first:first + count]

    def one(acc, e):
        wgu, wd, wt, j = e
        for gone in skip:
            wt = jnp.where(j == gone, 0.0, wt)
        term = jax.checkpoint(
            lambda x_, a, b, w_: w_[:, None] * swiglu(x_, a, b, mm))(x, wgu, wd, wt)
        return acc + term, None

    out, _ = lax.scan(one, jnp.zeros_like(x), (p["wgu"], p["wd"], w.T, jnp.arange(count)))
    if shared:
        out = out + swiglu(x, p["shared_wgu"], p["shared_wd"], mm)
    return out


def _sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def _mm(operand):
    return common.matmul(operand if operand == CONTROL else None)


def pairing(cfg, operand=None):
    """The rotation a run makes: the published pairing, or a control's."""
    if operand == "drop_rope":
        return None
    if operand == "half_split":
        return "half_split"
    return "interleave" if cfg["rope_interleave"] else "half_split"


def mixer(params, x, cfg, i, operand=None):
    """h = x + mla(rms(x)) of layer i on one sequence x [t, d]."""
    p = _sub(params, f"l{i}.")
    a = rms(x, p["norm1"], cfg["rms_norm_eps"])
    return x + mla(_sub(p, "mla."), a, cfg, _mm(operand), pairing(cfg, operand))


def ffn(params, h, cfg, i, operand=None):
    """y = h + ffn(rms(h)) of layer i."""
    mm = _mm(operand)
    p = _sub(params, f"l{i}.")
    a = rms(h, p["norm2"], cfg["rms_norm_eps"])
    if kinds(cfg)[i] == "dense":
        return h + swiglu(a, p["mlp.wgu"], p["mlp.wd"], mm)
    return h + moe(_sub(p, "moe."), a, cfg, mm,
                   skip=(0,) if operand == "drop_expert" else (),
                   shared=operand != "drop_shared",
                   ignore_bias=operand == "ignore_bias")


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
    log-softmax LOSS_ROWS tokens at a time (tie_word_embeddings false: the
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
# the reference's steps, lean: 576 M float32 parameters with their gradient
# and Adam's two moments are 9.2 GB of the chip's 16, so the starting weights
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
    the HOST meanwhile and visit the chip leaf by leaf. `params0`: host
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
    moments = None                                      # (m, v) on the host
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
        m, v = jax.device_put(moments) if moments else ({}, {})
        for k in list(params):
            g = grads.pop(k) / count
            if i == 0:
                grad_norms[k] = norm(g)
                m[k] = v[k] = jnp.zeros_like(g, F32)
            params[k], m[k], v[k] = adam(params[k], g, m[k], v[k], i + 1)
        moments = jax.device_get((m, v)) if i + 1 < len(batches) else None
        del m, v
    delta_norms = {k: norm(params[k] - np.asarray(params0[k])) for k in params}
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta_norms}
