"""Device scopes (telemetry/trace.py `device_scope`; docs/TELEMETRY.md
"Device scopes"): for a tiny model of each family the benchmark's cells
build, the REAL train step is compiled here on the CPU and the optimised
HLO's `op_name`s are read as the benchmark's reader reads a device trace's
`tf_op` (`benchmark/scope_reduce.py` `parse`): every product, loop, sort and
custom call sits under a layer's scope, `dl4j.loss` or `dl4j.update`; every
part of the grammar occurs on both passes; the written-out `custom_vjp`
backwards carry their part; and the scopes change nothing but names — the
step lowers to the same HLO with `jax.named_scope` patched to a null
context. The kernels' custom calls are checked on a TPU lowering (no
compile), where they exist."""
import contextlib
import glob
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from benchmark import scope_reduce
from benchmark.tests import tiny_kimi, tiny_nemotron
from deeplearning4j_tpu import telemetry, zoo
from deeplearning4j_tpu.telemetry import trace as trace_mod

T = 128


def _args(cfg):
    return {k: tuple(v) if isinstance(v, list) else v
            for k, v in cfg["program"]["args"].items()}


def gpt2(t=T, d_model=32, n_heads=4):
    return zoo.TransformerLM(num_classes=48, max_length=t, d_model=d_model,
                             n_heads=n_heads, n_layers=2)


def qwen(**kw):
    return zoo.HybridMoELM(**{**dict(
        vocab_size=48, hidden_size=32, num_hidden_layers=4, full_attention_interval=4,
        max_length=T, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
        linear_value_head_dim=8, num_experts=4, num_experts_published=8,
        experts_first=2, num_experts_per_tok=3, moe_intermediate_size=16,
        shared_expert_intermediate_size=16, capacity_factor=2.0, remat="full"), **kw})


def nemotron(**kw):
    return zoo.PatternHybridLM(**{**_args(tiny_nemotron.nemotron_h()), "remat": "full", **kw})


def kimi(**kw):
    return zoo.DeltaLatentMoELM(**{**_args(tiny_kimi.kimi_linear()), "remat": "full", **kw})


FAMILIES = {"gpt2": gpt2, "qwen3next": qwen, "nemotron": nemotron, "kimilinear": kimi}

#: per family: the (innermost kind, part path) pairs its step must show on
#: BOTH passes, and the kinds whose backward must carry a written-out part
EXPECT = {
    "gpt2": {
        ("transformerblock", ("norm",)), ("transformerblock", ("mlp",)),
        ("multiheadattention", ("proj",)), ("multiheadattention", ("attend",)),
        ("multiheadattention", ("out",))},
    "qwen3next": {
        ("hybridblock", ("norm",)),
        ("gateddeltanet", ("proj",)), ("gateddeltanet", ("retile",)),
        ("gateddeltanet", ("conv",)), ("gateddeltanet", ("gates",)),
        ("gateddeltanet", ("rule",)), ("gateddeltanet", ("rule", "solve")),
        ("gateddeltanet", ("rule", "scan")), ("gateddeltanet", ("norm_gate",)),
        ("gatedattention", ("proj",)), ("gatedattention", ("gates",)),
        ("gatedattention", ("attend",)), ("gatedattention", ("out",)),
        ("routedexperts", ("route",)), ("routedexperts", ("gather",)),
        ("routedexperts", ("product",)), ("routedexperts", ("combine",)),
        ("routedexperts", ("shared",))},
    "nemotron": {
        ("sublayerblock", ("norm",)),
        ("mamba2mixer", ("proj",)), ("mamba2mixer", ("retile",)),
        ("mamba2mixer", ("conv",)), ("mamba2mixer", ("gates",)),
        ("mamba2mixer", ("rule",)), ("mamba2mixer", ("rule", "scan")),
        ("mamba2mixer", ("norm_gate",)),
        ("gatedattention", ("proj",)), ("gatedattention", ("attend",)),
        ("gatedattention", ("out",)),
        ("routedexperts", ("route",)), ("routedexperts", ("gather",)),
        ("routedexperts", ("product",)), ("routedexperts", ("combine",)),
        ("routedexperts", ("shared",))},
    "kimilinear": {
        ("sublayerblock", ("norm",)), ("sublayerblock", ("mlp",)),
        ("kimideltaattention", ("proj",)), ("kimideltaattention", ("retile",)),
        ("kimideltaattention", ("conv",)), ("kimideltaattention", ("gates",)),
        ("kimideltaattention", ("rule",)), ("kimideltaattention", ("rule", "solve")),
        ("kimideltaattention", ("rule", "scan")), ("kimideltaattention", ("norm_gate",)),
        ("latentattention", ("proj",)), ("latentattention", ("gates",)),
        ("latentattention", ("attend",)), ("latentattention", ("out",)),
        ("routedexperts", ("route",)), ("routedexperts", ("gather",)),
        ("routedexperts", ("product",)), ("routedexperts", ("combine",)),
        ("routedexperts", ("shared",))},
}
#: forward-only parts (no cotangent reaches them): met on the forward pass
FORWARD_ONLY = {
    "gpt2": set(),
    "qwen3next": {("routedexperts", ("sort",)), ("routedexperts", ("counters",))},
    "nemotron": {("routedexperts", ("sort",)), ("routedexperts", ("counters",)),
                 ("mamba2mixer", ("counters",))},
    "kimilinear": {("routedexperts", ("sort",)), ("routedexperts", ("counters",)),
                   ("kimideltaattention", ("counters",))},
}
#: HLO opcodes that stand for real work and must never be orphans
WORK = re.compile(r" (dot|ragged-dot|while|sort|custom-call|triangular-solve)\(")


def lowered(net, platform=None, t=T):
    """The net's raw train step, integer labels, lowered (for `platform`,
    else here)."""
    ids = jnp.zeros((2, t), jnp.int32)
    args = (net.params, net.state, net.opt_state, jnp.int32(0), jax.random.PRNGKey(0),
            ids, ids, None, None)
    traced = jax.jit(net._train_step_fn()).trace(*args)
    return traced.lower(lowering_platforms=(platform,)) if platform else traced.lower()


def hlo_text(low, metadata: bool) -> str:
    """The lowering's (unoptimised) HLO: with `metadata={..}`, or in XLA's
    canonical form (what a module's fingerprint is taken of: no metadata,
    and no instruction names either — the converter derives those from
    `op_name`)."""
    from jax._src.lib import xla_client as xc

    if metadata:
        opts = xc._xla.HloPrintOptions()
        opts.print_metadata = True
        opts.print_backend_config = False
    else:
        opts = xc._xla.HloPrintOptions.fingerprint()
    return low.compiler_ir(dialect="hlo").get_hlo_module().to_string(opts)


@pytest.fixture(scope="module")
def steps():
    """family -> [(HLO instruction line, op_name)] of the compiled step."""
    cache = {}

    def get(family):
        if family not in cache:
            text = lowered(FAMILIES[family]().init()).compile().as_text()
            cache[family] = [(line, m.group(1)) for line in text.splitlines()
                             for m in [re.search(r'op_name="([^"]*)"', line)] if m]
        return cache[family]

    return get


# ---------------------------------------------------------------------------
# the seam
# ---------------------------------------------------------------------------
def test_seam_spells_the_grammar():
    def f(x):
        with telemetry.device_scope(kind="KimiDeltaAttention", layer=3):
            with telemetry.device_scope("rule"), telemetry.device_scope("solve"):
                x = x * 2
        with telemetry.device_scope(kind="loss"):
            return x + 1

    text = jax.jit(f).lower(jnp.ones(3)).as_text(debug_info=True)
    assert "jit(f)/dl4j.L3.kimideltaattention/rule/solve/mul" in text
    assert "jit(f)/dl4j.loss/add" in text
    # a graph vertex's name cannot break the path
    with telemetry.device_scope(kind="Dense", layer="in/a.b") as stack:
        assert str(stack).endswith("dl4j.Lin_a_b.dense")


@pytest.mark.parametrize("bad", [dict(part="prj"), dict(part="proj", layer=1), dict(),
                                 dict(part="proj", kind="loss")])
def test_seam_refuses_what_the_grammar_lacks(bad):
    part = bad.pop("part", None)
    with pytest.raises(ValueError):
        telemetry.device_scope(part, **bad)


def test_the_prefix_is_spelled_in_the_seam_alone():
    root = os.path.dirname(os.path.dirname(trace_mod.__file__))
    spelled = [p for p in glob.glob(os.path.join(root, "**", "*.py"), recursive=True)
               if re.search(r'named_scope\(|"dl4j\.L|SCOPE_PREFIX', open(p).read())]
    assert spelled == [trace_mod.__file__]
    assert "jax.named_scope" in open(trace_mod.__file__).read()


# ---------------------------------------------------------------------------
# the parser, on the name stacks JAX makes of this repo's constructs
# ---------------------------------------------------------------------------
S = scope_reduce.Scope
STACKS = [
    ("jit(loss)/jvp(dl4j.L0.kda)/proj/dot_general",
     S("0", "kda", ("proj",), False, False, 0)),
    ("jit(loss)/jvp(dl4j.L0.kda)/closed_call/while/body/closed_call/scan/mul",
     S("0", "kda", ("scan",), False, False, 0)),
    ("jit(loss)/transpose(jvp(jvp()))/checkpoint/dl4j.L0.kda/proj/transpose",
     S("0", "kda", ("proj",), True, False, 1)),
    ("jit(loss)/transpose(jvp(jvp()))/checkpoint/dl4j.L0.kda/while/body/closed_call/scan/mul",
     S("0", "kda", ("scan",), True, False, 1)),
    # a nested kind wins: what a block opens around a layer nested in it
    ("jit(loss)/transpose(jvp(jvp()))/checkpoint/dl4j.L0.kda/conv/dl4j.cv/cos",
     S("0", "cv", ("conv",), True, False, 1)),
    # as the chip's trace spells it: ":" and an op type behind the stack
    ("jit(step)/transpose(jvp(dl4j.L5.sublayerblock))/dl4j.routedexperts/product/ragged_dot: fusion",
     S("5", "routedexperts", ("product",), True, False, 0)),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/dl4j.L2.hybridblock/"
     "dl4j.gateddeltanet/while/body/closed_call/checkpoint/rule/solve/jit(_solve_triangular)/"
     "triangular_solve:", S("2", "gateddeltanet", ("rule", "solve"), True, True, 2)),
    # a jitted function named like a part is no part; nor is the primitive
    ("jit(step)/jvp(dl4j.L1.rmsnorm)/jit(norm)/sort", S("1", "rmsnorm", (), False, False, 0)),
    ("jit(step)/dl4j.loss/while/body/dot_general:", S(None, "loss", (), False, False, 0)),
    ("jit(step)/dl4j.update/mul", S(None, "update", (), False, False, 0)),
    ("jit(small_step)/dot_general:", None),
]


@pytest.mark.parametrize("stack,want", STACKS, ids=[str(i) for i in range(len(STACKS))])
def test_parser(stack, want):
    assert scope_reduce.parse(stack) == want


# ---------------------------------------------------------------------------
# the compiled step of each family
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("family", FAMILIES)
def test_no_work_is_an_orphan(steps, family):
    work = [(line, name) for line, name in steps(family) if WORK.search(line)]
    assert len(work) > 20
    orphans = [name for _, name in work if scope_reduce.parse(name) is None]
    assert not orphans, orphans[:10]
    kinds = {scope_reduce.parse(name).kind for _, name in work}
    assert {"loss", "update"} & kinds == {"loss"}       # the update is elementwise
    assert any(s is not None and s.kind == "update"
               for s in (scope_reduce.parse(n) for _, n in steps(family)))


@pytest.mark.parametrize("family", FAMILIES)
def test_every_part_on_both_passes(steps, family):
    seen = {False: set(), True: set()}
    for _, name in steps(family):
        s = scope_reduce.parse(name)
        if s is not None:
            for i in range(len(s.parts) + 1):       # ("rule", "solve") shows "rule" too
                seen[s.backward].add((s.kind, s.parts[:i]))
    assert EXPECT[family] <= seen[False], EXPECT[family] - seen[False]
    assert EXPECT[family] <= seen[True], EXPECT[family] - seen[True]
    assert FORWARD_ONLY[family] <= seen[False], FORWARD_ONLY[family] - seen[False]
    # every layer of the net has a scope of its own index, and the loss
    layers = {scope_reduce.parse(n).layer for _, n in steps(family) if scope_reduce.parse(n)}
    n_layers = len(FAMILIES[family]().conf().layers)
    assert {str(i) for i in range(n_layers - 1)} | {None} == layers


@pytest.mark.parametrize("family", FAMILIES)
def test_the_loss_makes_its_gradient_in_its_forward_visit(steps, family):
    """Integer labels on softmax + mcxent: the row-blocked head + loss
    (`losses.sparse_xent_weighted`) forms a block's gradient under the part
    `grad`, on the forward pass by the reader's grammar (no `transpose(`),
    and nothing of the loss is run again: three products in all."""
    loss = [(line, s) for line, n in steps(family)
            for s in [scope_reduce.parse(n)] if s is not None and s.kind == "loss"]
    assert any(s.parts == ("grad",) and not s.backward for _, s in loss)
    assert not any(s.recompute for _, s in loss)
    products = [s for line, s in loss if re.search(r" (dot|convolution)\(", line)]
    assert len(products) == 3 and [s.parts for s in products].count(("grad",)) == 2
    assert not any(s.backward for s in products)


@pytest.mark.parametrize("family", ["qwen3next", "nemotron", "kimilinear"])
def test_written_out_backwards_carry_their_part(steps, family):
    """`_conv_silu_bwd`, `_to_buffer_bwd`, `_from_buffer_bwd` (and
    `_decayed_scores_bwd` under `rule`) are traced with the stack of their
    call site: the part it was opened in."""
    back = {(s.kind, s.parts[:1]) for _, n in steps(family)
            for s in [scope_reduce.parse(n)] if s is not None and s.backward and not s.recompute}
    mixer = {"qwen3next": "gateddeltanet", "nemotron": "mamba2mixer",
             "kimilinear": "kimideltaattention"}[family]
    assert {(mixer, ("conv",)), ("routedexperts", ("gather",)),
            ("routedexperts", ("combine",))} <= back
    # the shifted reads of the convolution's backward are pads under `conv`
    assert any(" pad(" in line and s.parts[:1] == ("conv",) and s.backward
               for line, n in steps(family) for s in [scope_reduce.parse(n)] if s)


@pytest.mark.parametrize("family", FAMILIES)
def test_scopes_change_nothing_but_names(family):
    net = FAMILIES[family]().init()
    scoped = lowered(net)
    assert "dl4j.L1." in hlo_text(scoped, metadata=True)
    with mock.patch("jax.named_scope", lambda name: contextlib.nullcontext()):
        bare = lowered(net)
    assert "dl4j." not in hlo_text(bare, metadata=True)
    assert hlo_text(scoped, metadata=False) == hlo_text(bare, metadata=False)


# ---------------------------------------------------------------------------
# the kernels' custom calls, where they exist: a TPU lowering
# ---------------------------------------------------------------------------
def _custom_calls(net, t):
    # Mosaic lowers no float64: the flash kernels' scalars are weakly typed
    with mock.patch("jax.default_backend", return_value="tpu"), \
            jax.enable_x64(False):
        text = hlo_text(lowered(net, "tpu", t), metadata=True)
    return [m.group(1) for line in text.splitlines() if "tpu_custom_call" in line
            for m in [re.search(r'op_name="([^"]*)"', line)] if m]


def test_flash_kernels_sit_under_attend():
    calls = _custom_calls(gpt2(t=512, d_model=128, n_heads=2).init(), 512)   # heads of 64
    scopes = [scope_reduce.parse(n) for n in calls]
    assert len(calls) == 4 and all("dl4j_flash_" in n for n in calls)     # 2 layers: fwd, bwd
    assert all(s.kind == "multiheadattention" and s.parts == ("attend",) for s in scopes)
    assert {s.backward for s in scopes} == {False, True}
    assert {s.layer for s in scopes} == {"2", "3"}


def test_kda_kernels_sit_under_rule():
    lin = dict(tiny_kimi.kimi_linear()["program"]["args"]["linear_attn_config"],
               head_dim=128, num_heads=1)
    calls = _custom_calls(kimi(linear_attn_config=lin, remat=None).init(), T)
    kda = [scope_reduce.parse(n) for n in calls if "dl4j_kda_" in n]
    assert kda and all(s.kind == "kimideltaattention" and s.parts == ("rule",) for s in kda)
    assert {s.backward for s in kda} == {False, True}
    # the short convolution's kernels in the same step: under `conv`, the
    # backward with the stack of its call site
    conv = [scope_reduce.parse(n) for n in calls if "dl4j_convsilu_" in n]
    assert conv and all(s.kind == "kimideltaattention" and s.parts == ("conv",) for s in conv)
    assert {s.backward for s in conv} == {False, True}
