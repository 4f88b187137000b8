"""ShardedTransformerLM — dp × tp × sp × pp × ep transformer training.

The reference's ONLY parallelism is data parallelism (SURVEY.md §2.4:
"no tensor / pipeline / sequence / expert parallelism anywhere in the
tree"). This module is the TPU-first generalization the north star
requires: one training step that composes

  dp — batch sharded over "data"; gradient psum (replaces ParallelWrapper
       averaging / EncodedGradientsAccumulator fan-out),
  tp — Megatron tensor parallelism over "model": attention heads and FFN
       hidden dim sharded; forward psum after row-split matmuls
       (g-operator), identity-fwd/psum-bwd at branch entry (f-operator),
  sp — sequence (context) parallelism over "seq": activations sharded
       along time, exact attention via ring ppermute (ops/ring.py),
       position table indexed at global offsets,
  pp — GPipe pipeline parallelism over "pipe": transformer blocks stored
       STACKED [n_layers, ...] and sharded on the layer axis; microbatches
       flow stage-to-stage via ppermute; autodiff of ppermute gives the
       exact reverse schedule for backward,
  ep — expert parallelism over "expert": optional Switch-style top-1 MoE
       FFN with expert weights sharded over the axis; each shard computes
       its local experts' tokens, the combine is a psum (g-operator), the
       router stays replicated with complete gradients (gate applied
       AFTER the combine),

all inside ONE `jax.shard_map` whose collectives XLA lowers onto ICI. The
optimizer step reuses the framework Updater suite and runs on the sharded
grads under the same jit, so params/opt state never gather.

Gradient correctness policy: no cross-shard psum is ever differentiated
(their transposes under check_vma=False overcount). Forward reductions are
explicit custom-vjp g-operators; the loss normalizer is computed OUTSIDE
the grad; grads get primal psums over (data, seq) plus "pipe" for leaves
not sharded by stage. Every mesh factorization reproduces the single-chip
loss trajectory to f32 roundoff (tests/test_sharded_transformer.py).

Parameters are stored FULL-SIZE on host; `shard()` places them with the
NamedShardings implied by `param_specs()` and shard_map slices them. This
keeps checkpointing (ModelSerializer contract) oblivious to the mesh.
"""
# jaxlint: disable-file=JX018 — this module IS the tp/sp/pp/ep placement
# implementation (predates parallel/layout.py); its specs are the Megatron
# sharding rules themselves, mirrored by layout.py's fsdp extension

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.nn import updaters as upd_mod
from deeplearning4j_tpu.ops import ring
from deeplearning4j_tpu.parallel import layout as layout_mod

PyTree = Any


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _copy_to_model(x, axis):
    """Megatron f-operator: identity forward; backward psums cotangents over
    the tensor (or expert) axis so replicated-param grads upstream of a
    sharded branch are complete on every shard."""
    return x


def _ctm_fwd(x, axis):
    return x, None


def _ctm_bwd(axis, _, g):
    return (lax.psum(g, axis),)


_copy_to_model.defvjp(_ctm_fwd, _ctm_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _reduce_from_model(x, axis):
    """Megatron g-operator: psum partial row-parallel (or per-expert)
    outputs; backward is identity (the output is replicated downstream, so
    each shard's cotangent is already the full dL/dy). Explicit custom_vjp
    because the autodiff transpose of a raw psum under check_vma=False
    would psum the already-replicated cotangent again — an axis-fold
    overcount."""
    return lax.psum(x, axis)


def _rfm_fwd(x, axis):
    return lax.psum(x, axis), None


def _rfm_bwd(axis, _, g):
    return (g,)


_reduce_from_model.defvjp(_rfm_fwd, _rfm_bwd)


@dataclass
class TransformerConfig:
    vocab: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    ffn_mult: int = 4
    max_len: int = 2048
    n_experts: int = 0           # 0 = dense FFN; >0 = Switch top-1 MoE
    expert_ffn_mult: Optional[int] = None  # default: ffn_mult
    microbatches: Optional[int] = None     # pipeline depth (default: pp)
    #: per-block activation-checkpoint policy: 'none' | 'dots_saveable' |
    #: 'full' | 'offload' (parallel/layout.py registry). Bools stay
    #: accepted for old configs/checkpoints: True='full', False='none'.
    remat: Any = True            # jax.checkpoint per block (HBM ↔ FLOPs)
    dtype: Any = jnp.float32     # params/activations; MXU runs bf16 anyway
    #: sub-chunk each ring-attention hop's K/V so per-chip attention
    #: memory is O(t_loc * attention_block) instead of O(t_loc^2) —
    #: required when per-device shards run long (ring.py _hop_update)
    attention_block: Optional[int] = 512

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


class ShardedTransformerLM:
    """Decoder-only LM with tied embeddings, pre-LN blocks, causal ring
    attention. Axis names must exist in the mesh (size-1 axes are fine, so
    the same code runs 1-chip and pod-scale)."""

    def __init__(self, config: TransformerConfig, mesh: Mesh,
                 updater: Optional[upd_mod.Updater] = None,
                 data_axis: str = "data", model_axis: str = "model",
                 seq_axis: str = "seq", pipe_axis: str = "pipe",
                 expert_axis: str = "expert"):
        c = config
        if c.d_model % c.n_heads:
            raise ValueError("n_heads must divide d_model")
        tp = mesh.shape[model_axis]
        if c.n_heads % tp:
            raise ValueError(f"tp={tp} must divide n_heads={c.n_heads}")
        if (c.ffn_mult * c.d_model) % tp:
            raise ValueError("tp must divide ffn hidden dim")
        pp = mesh.shape[pipe_axis]
        if c.n_layers % pp:
            raise ValueError(f"pp={pp} must divide n_layers={c.n_layers}")
        ep = mesh.shape[expert_axis]
        if ep > 1 and c.n_experts == 0:
            raise ValueError("expert axis > 1 requires n_experts > 0")
        if c.n_experts and c.n_experts % ep:
            raise ValueError(f"ep={ep} must divide n_experts={c.n_experts}")
        self.config = c
        self.mesh = mesh
        self.updater = updater or upd_mod.Adam(learning_rate=3e-4)
        self.ax_d, self.ax_m, self.ax_s = data_axis, model_axis, seq_axis
        self.ax_p, self.ax_e = pipe_axis, expert_axis
        self.params: Optional[PyTree] = None
        self.opt_state: Optional[PyTree] = None
        self._step_fn = None
        self._fwd_fn = None
        self.iteration = 0
        self.score_ = float("nan")

    @property
    def _pp(self) -> int:
        return self.mesh.shape[self.ax_p]

    # ---------------- params ----------------
    def init(self, seed: int = 0) -> "ShardedTransformerLM":
        self.params = self._init_params(seed)
        self.opt_state = self.updater.init_state(self.params)
        self.shard()
        return self

    def _init_params(self, seed: int) -> PyTree:
        c = self.config
        key = jax.random.PRNGKey(seed)
        ks = jax.random.split(key, 2 + c.n_layers)
        dt = c.dtype
        D, H, dh = c.d_model, c.n_heads, c.head_dim
        F = c.ffn_mult * D
        E = c.n_experts
        Fe = (c.expert_ffn_mult or c.ffn_mult) * D

        def norm(k, shape, std):
            return jax.random.normal(k, shape, dt) * std

        blocks = []
        for i in range(c.n_layers):
            bk = jax.random.split(ks[2 + i], 6)
            blk = {
                "ln1": {"g": jnp.ones((D,), dt), "b": jnp.zeros((D,), dt)},
                "Wqkv": norm(bk[0], (D, 3, H, dh), D ** -0.5),
                "bqkv": jnp.zeros((3, H, dh), dt),
                "Wo": norm(bk[1], (H, dh, D), (H * dh) ** -0.5),
                "bo": jnp.zeros((D,), dt),
                "ln2": {"g": jnp.ones((D,), dt), "b": jnp.zeros((D,), dt)},
            }
            if E:
                blk.update({
                    "Wr": norm(bk[2], (D, E), D ** -0.5),
                    "We1": norm(bk[3], (E, D, Fe), D ** -0.5),
                    "be1": jnp.zeros((E, Fe), dt),
                    "We2": norm(bk[4], (E, Fe, D), Fe ** -0.5),
                    "be2": jnp.zeros((E, D), dt),
                })
            else:
                blk.update({
                    "W1": norm(bk[2], (D, F), D ** -0.5),
                    "b1": jnp.zeros((F,), dt),
                    "W2": norm(bk[3], (F, D), F ** -0.5),
                    "b2": jnp.zeros((D,), dt),
                })
            blocks.append(blk)
        # stack per-layer leaves: [n_layers, ...], sharded over the pipe axis
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
        return {
            "embed": norm(ks[0], (c.vocab, D), 0.02),
            "pos": norm(ks[1], (c.max_len, D), 0.02),
            "blocks": stacked,
            "lnf": {"g": jnp.ones((D,), dt), "b": jnp.zeros((D,), dt)},
        }

    def param_specs(self) -> PyTree:
        m, p, e = self.ax_m, self.ax_p, self.ax_e
        blk = {
            "ln1": {"g": P(p), "b": P(p)},
            "Wqkv": P(p, None, None, m, None),
            "bqkv": P(p, None, m, None),
            "Wo": P(p, m, None, None),
            "bo": P(p),
            "ln2": {"g": P(p), "b": P(p)},
        }
        if self.config.n_experts:
            blk.update({
                "Wr": P(p, None, None),
                "We1": P(p, e, None, None),
                "be1": P(p, e, None),
                "We2": P(p, e, None, None),
                "be2": P(p, e, None),
            })
        else:
            blk.update({
                "W1": P(p, None, m),
                "b1": P(p, m),
                "W2": P(p, m, None),
                "b2": P(p),
            })
        return {
            "embed": P(),
            "pos": P(),
            "blocks": blk,
            "lnf": {"g": P(), "b": P()},
        }

    def shard(self):
        """Place params/opt state on the mesh per param_specs()."""
        specs = self.param_specs()
        self.params = _put_tree(self.mesh, self.params, specs)
        if self.opt_state is not None:
            self.opt_state = _put_opt_state(self.mesh, self.opt_state, specs)

    # ---------------- blocks ----------------
    def _moe(self, p, m_in):
        """Switch-style top-1 MoE FFN, experts sharded over ax_e.
        Gate applied AFTER the psum combine so the replicated router's
        gradients are complete on every expert shard."""
        dt = m_in.dtype
        r = m_in @ p["Wr"]                       # [b, t, E] replicated
        probs = jax.nn.softmax(r, axis=-1)
        gate = probs.max(axis=-1)                # [b, t]
        assign = probs.argmax(axis=-1)           # [b, t]
        x_in = _copy_to_model(m_in, self.ax_e)
        el = p["We1"].shape[0]                   # local experts
        e0 = lax.axis_index(self.ax_e) * el
        acc = jnp.zeros_like(m_in)
        for j in range(el):
            sel = (assign == e0 + j).astype(dt)[..., None]
            h = jax.nn.gelu(x_in @ p["We1"][j] + p["be1"][j])
            h = h @ p["We2"][j] + p["be2"][j]
            acc = acc + sel * h
        combined = _reduce_from_model(acc, self.ax_e)
        return gate[..., None] * combined

    def _block(self, p, h):
        c = self.config
        b, tl, D = h.shape
        tp_heads = p["Wqkv"].shape[2]  # local heads after shard_map slicing
        dh = c.head_dim

        a_in = _copy_to_model(_ln(p["ln1"], h), self.ax_m)
        qkv = jnp.einsum("btd,dchk->bcthk", a_in, p["Wqkv"]) \
            + p["bqkv"][None, :, None, :, :]
        q = qkv[:, 0].transpose(0, 2, 1, 3)
        k = qkv[:, 1].transpose(0, 2, 1, 3)
        v = qkv[:, 2].transpose(0, 2, 1, 3)
        o = ring.ring_attention_sharded(
            q, k, v, axis_name=self.ax_s, causal=True,
            block_size=c.attention_block)
        o = o.transpose(0, 2, 1, 3).reshape(b, tl, tp_heads * dh)
        wo = p["Wo"].reshape(tp_heads * dh, D)
        a = _reduce_from_model(o @ wo, self.ax_m) + p["bo"]
        h = h + a

        if c.n_experts:
            mlp = self._moe(p, _ln(p["ln2"], h))
        else:
            m_in = _copy_to_model(_ln(p["ln2"], h), self.ax_m)
            hid = jax.nn.gelu(m_in @ p["W1"] + p["b1"])
            mlp = _reduce_from_model(hid @ p["W2"], self.ax_m) + p["b2"]
        return h + mlp

    def _stage(self, blocks, h):
        """Apply this device's slice of the stacked blocks sequentially."""
        n_local = jax.tree_util.tree_leaves(blocks)[0].shape[0]
        blk = layout_mod.maybe_remat(self._block, self.config.remat)
        for i in range(n_local):
            p_i = jax.tree_util.tree_map(lambda a: a[i], blocks)
            h = blk(p_i, h)
        return h

    # ---------------- forward ----------------
    def _forward_local(self, params, ids):
        """ids [b_loc, t_loc] -> logits [b_loc, t_loc, vocab]; runs inside
        shard_map. With pp > 1 the blocks execute as a GPipe microbatch
        pipeline; logits are psum-broadcast from the last stage."""
        c = self.config
        b, tl = ids.shape
        t_off = lax.axis_index(self.ax_s) * tl
        h = jnp.take(params["embed"], ids, axis=0)
        pos = lax.dynamic_slice_in_dim(params["pos"], t_off, tl, axis=0)
        h = h + pos[None]

        pp = self._pp
        if pp == 1:
            h = self._stage(params["blocks"], h)
        else:
            h = self._pipeline(params["blocks"], h, pp)
        h = _ln(params["lnf"], h)
        logits = h @ params["embed"].T
        if pp > 1:
            stage = lax.axis_index(self.ax_p)
            logits = _reduce_from_model(
                jnp.where(stage == pp - 1, logits, 0.0), self.ax_p)
        return logits

    def _pipeline(self, blocks, h, pp: int):
        """GPipe schedule: M microbatches, pp stages, M+pp-1 steps; stage
        outputs hop to the next stage via ppermute (no wraparound). The
        autodiff transpose of ppermute is the inverted permutation, so the
        backward pass is the exact reverse pipeline for free."""
        c = self.config
        b, tl, D = h.shape
        M = c.microbatches or pp
        if b % M:
            raise ValueError(f"local batch {b} must divide into "
                             f"microbatches={M}")
        bm = b // M
        x_mb = h.reshape(M, bm, tl, D)
        outputs = jnp.zeros_like(x_mb)
        carry = jnp.zeros((bm, tl, D), h.dtype)
        stage = lax.axis_index(self.ax_p)
        fwd_perm = [(i, i + 1) for i in range(pp - 1)]
        for step in range(M + pp - 1):
            mb = x_mb[min(step, M - 1)]
            inp = jnp.where(stage == 0, mb, carry)
            out = self._stage(blocks, inp)
            out_idx = step - (pp - 1)
            if out_idx >= 0:
                keep = jnp.where(stage == pp - 1, out, outputs[out_idx])
                outputs = outputs.at[out_idx].set(keep)
            if step != M + pp - 2:
                carry = lax.ppermute(out, self.ax_p, fwd_perm)
        return outputs.reshape(b, tl, D)

    def _local_loss(self, params, ids, targets, weights, total_count):
        """Local shard's share of the global mean NLL. `total_count` is the
        params-independent psum of weights, computed OUTSIDE the grad — no
        cross-shard psum is differentiated. Under pp the term is masked to
        the LAST stage only: exactly one cotangent seed enters the pipeline
        and the transposed ppermutes carry it back through every stage
        (seeding all stages would overcount through the identity-backward
        logits broadcast)."""
        logits = self._forward_local(params, ids)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        local_sum = jnp.sum(nll * weights)
        pp = self._pp
        if pp > 1:
            stage = lax.axis_index(self.ax_p)
            local_sum = jnp.where(stage == pp - 1, local_sum, 0.0)
        return local_sum / total_count

    # ---------------- training ----------------
    def _grad_reduce_axes(self, spec) -> Tuple[str, ...]:
        """Primal psum axes for a grad leaf: always (data, seq); plus pipe
        for stage-replicated leaves (embed/pos/lnf — their compute is
        partitioned across stages, so per-stage grads are partial). Never
        model/expert: f/g operators already complete those cotangents, and
        sharded leaves' grads are local by construction."""
        axes = [self.ax_d, self.ax_s]
        mentioned = {a for part in spec if part is not None
                     for a in ((part,) if isinstance(part, str) else part)}
        if self._pp > 1 and self.ax_p not in mentioned:
            axes.append(self.ax_p)
        return tuple(axes)

    def _build_step(self):
        specs = self.param_specs()
        d, s = self.ax_d, self.ax_s
        x_spec = P(d, s)

        def sharded_grads(params, ids, targets, weights):
            total = lax.psum(jnp.sum(weights), (d, s))
            total = jnp.maximum(total, 1.0)
            local_loss, grads = jax.value_and_grad(self._local_loss)(
                params, ids, targets, weights, total)
            grads = jax.tree_util.tree_map(
                lambda g, sp: lax.psum(g, self._grad_reduce_axes(sp)),
                grads, specs, is_leaf=lambda n: isinstance(n, P))
            loss = lax.psum(local_loss, (d, s, self.ax_p))
            return loss, grads

        smapped = jax.shard_map(
            sharded_grads, mesh=self.mesh,
            in_specs=(specs, x_spec, x_spec, x_spec),
            out_specs=(P(), specs),
            check_vma=False,
        )

        def step(params, opt_state, ids, targets, weights):
            loss, grads = smapped(params, ids, targets, weights)
            steps, opt_state = self.updater.apply(
                grads, opt_state, self.updater.learning_rate)
            params = jax.tree_util.tree_map(jnp.subtract, params, steps)
            return params, opt_state, loss

        return jax.jit(step, donate_argnums=(0, 1))

    def fit_batch(self, ids: np.ndarray, targets: np.ndarray,
                  weights: Optional[np.ndarray] = None) -> float:
        """One SPMD training step. ids/targets [b, t] int32; weights [b, t]
        (1.0 = count this token) defaults to all-ones."""
        if self._step_fn is None:
            self._step_fn = self._build_step()
        if weights is None:
            weights = np.ones(ids.shape, np.float32)
        ids_s = _put_data(self.mesh, ids.astype(np.int32),
                          (self.ax_d, self.ax_s))
        tgt_s = _put_data(self.mesh, targets.astype(np.int32),
                          (self.ax_d, self.ax_s))
        w_s = _put_data(self.mesh, weights.astype(np.float32),
                        (self.ax_d, self.ax_s))
        self.params, self.opt_state, loss = self._step_fn(
            self.params, self.opt_state, ids_s, tgt_s, w_s)
        self.iteration += 1
        self.score_ = float(jax.device_get(loss))
        return self.score_

    # ---------------- persistence ----------------
    def save(self, path: str, save_updater: bool = True) -> None:
        """ModelSerializer zip contract (util/ModelSerializer.java:79) for
        the sharded model: params/opt state are jax global Arrays, so
        device_get gathers the FULL tensors regardless of how the mesh
        factorized them — the checkpoint is mesh-oblivious by
        construction (the docstring's contract, now enforced by
        tests/test_sharded_transformer.py round-trip)."""
        import dataclasses
        import json
        import zipfile

        from deeplearning4j_tpu.models.serialization import (
            FORMAT_VERSION,
            _tree_to_npz_bytes,
        )

        cfg = dataclasses.asdict(self.config)
        cfg["dtype"] = np.dtype(self.config.dtype).name
        host_params = jax.device_get(self.params)
        with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as z:
            z.writestr("configuration.json", json.dumps({
                "transformer_config": cfg,
                "updater": self.updater.to_json(),
            }))
            z.writestr("coefficients.npz", _tree_to_npz_bytes(host_params))
            if save_updater and self.opt_state is not None:
                z.writestr("updaterState.npz",
                           _tree_to_npz_bytes(jax.device_get(self.opt_state)))
            z.writestr("metadata.json", json.dumps({
                "format_version": FORMAT_VERSION,
                "model_type": "ShardedTransformerLM",
                "iteration": int(self.iteration),
            }))

    @classmethod
    def restore(cls, path: str, mesh: Mesh, load_updater: bool = True,
                **axis_kwargs) -> "ShardedTransformerLM":
        """Restore onto ANY mesh (the factorization need not match the
        one that saved): full-size host tensors are re-placed per the new
        mesh's param_specs, so a model trained dp x tp can resume dp x sp
        on a different chip count."""
        import json
        import zipfile

        from deeplearning4j_tpu.models.serialization import (
            _load_npz,
            _npz_restore_into,
        )
        from deeplearning4j_tpu.nn import updaters as upd_mod

        with zipfile.ZipFile(path, "r") as z:
            conf = json.loads(z.read("configuration.json").decode())
            meta = json.loads(z.read("metadata.json").decode())
            if meta.get("model_type") != "ShardedTransformerLM":
                raise ValueError(
                    f"{path}: not a ShardedTransformerLM checkpoint "
                    f"(model_type={meta.get('model_type')!r}); use "
                    f"models.serialization.restore_model")
            cfg_d = dict(conf["transformer_config"])
            cfg_d["dtype"] = np.dtype(cfg_d["dtype"])
            config = TransformerConfig(**cfg_d)
            updater = upd_mod.from_json(conf["updater"])
            lm = cls(config, mesh, updater=updater, **axis_kwargs)
            # pytree TEMPLATES only — eval_shape traces _init_params
            # without computing random weights or touching devices (a
            # real init would double restore time and peak memory)
            p_tmpl = jax.eval_shape(lambda: lm._init_params(0))
            coeff = _load_npz(z, "coefficients.npz")
            lm.params = _npz_restore_into(p_tmpl, coeff)
            upd = _load_npz(z, "updaterState.npz") if load_updater else None
            if upd is not None:
                o_tmpl = jax.eval_shape(
                    lambda: lm.updater.init_state(lm._init_params(0)))
                lm.opt_state = _npz_restore_into(o_tmpl, upd)
            else:
                lm.opt_state = lm.updater.init_state(lm.params)
            lm.iteration = int(meta.get("iteration", 0))
            lm.shard()  # place per THIS mesh's specs
        return lm

    def logits(self, ids: np.ndarray) -> np.ndarray:
        """Inference forward (same sharded path, no grad)."""
        if self._fwd_fn is None:
            specs = self.param_specs()
            x_spec = P(self.ax_d, self.ax_s)
            self._fwd_fn = jax.jit(jax.shard_map(
                self._forward_local, mesh=self.mesh,
                in_specs=(specs, x_spec),
                out_specs=P(self.ax_d, self.ax_s, None),
                check_vma=False,
            ))
        ids_s = _put_data(self.mesh, ids.astype(np.int32),
                          (self.ax_d, self.ax_s))
        return np.asarray(jax.device_get(self._fwd_fn(self.params, ids_s)))


def _ln(p, x, eps: float = 1e-5):
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def _put_tree(mesh, tree, specs):
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        tree, specs, is_leaf=lambda n: isinstance(n, P),
    )


def _put_opt_state(mesh, opt_state, specs):
    """Shard optimizer moment trees like their params; scalars replicate."""
    out = {}
    for k, v in opt_state.items():
        if isinstance(v, (dict, list)) and _mirrors(v, specs):
            out[k] = _put_tree(mesh, v, specs)
        else:
            out[k] = jax.device_put(v, NamedSharding(mesh, P()))
    return out


def _mirrors(tree, specs) -> bool:
    try:
        jax.tree_util.tree_map(lambda a, b: None, tree, specs,
                               is_leaf=lambda n: isinstance(n, P))
        return True
    except (ValueError, TypeError):
        return False


def _put_data(mesh, arr, axes: Tuple[str, str]):
    spec = P(*axes) if arr.ndim == 2 else P(axes[0])
    return jax.device_put(arr, NamedSharding(mesh, spec))
