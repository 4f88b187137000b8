"""The per-layer readers that find their work by the program's scopes
(PR 51): the recurrent cores' shares and the three chunk rules' rooflines,
the experts' share, the primal forward's share with `loss.grad` booked
backward, the recompute share — on steps built by hand, WITH and WITHOUT a
`while` around the core (whatever holds the work, the reader reads the same),
and on the hybrid step `record_scoped_trace.py hybrid` recorded on a v5e."""
import gzip
import json
import os
import re
from types import SimpleNamespace as NS

import pytest

from benchmark import harness, scope_reduce as sr, trace_reduce
from benchmark.tests.test_hybrid_metrics import config, read
from benchmark.tests.test_scope_reduce import fake_run, meta

HERE = os.path.dirname(os.path.abspath(__file__))
HYBRID = os.path.join(HERE, "scoped_hybrid_tpu.xplane.pb.gz")
PARTS = frozenset({"proj", "out", "rule", "conv", "gates", "norm_gate", "retile", "counters",
                   "route", "sort", "gather", "product", "combine", "shared", "grad", "norm"})
CORES = {  # the mixer's kind -> (configuration, its share reader, its roofline, flops, bytes)
    "gateddeltanet": ("qwen3-next-80b-a3b-l4", "delta_core_share_of_step.train",
                      "gdn_roofline.train", "gdn_flops", "gdn_bytes"),
    "kimideltaattention": ("kimi-linear-48b-a3b-l5", "kda_share_of_step.train",
                           "kda_roofline.train", "kda_flops", "kda_bytes"),
    "mamba2mixer": ("nemotron-3-nano-30b-a3b-l9", "ssd_share_of_step.train",
                    "ssd_roofline.train", "ssd_flops", "ssd_bytes"),
}
REMOVED = ("delta_scan_share_of_step.train", "delta_scan_roofline.train",
           "delta_solve_share_of_step.train")
ADDED = ("gdn_roofline.train", "recompute_share_of_step.train", "step_wall_median_ms.train")


def stacks(kind, layer="L1.hybridblock"):
    fwd = f"jit(step)/jvp(dl4j.{layer})/dl4j.{kind}"
    bwd = f"jit(step)/transpose(jvp(dl4j.{layer}))/dl4j.{kind}"
    again = f"jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/dl4j.{layer}/dl4j.{kind}"
    return fwd, bwd, again


def mixer_step(kind, loop):
    """Two runs of 1000 ns of a step whose mixer is `kind`. Forward: the in
    projection 100 ns, the core 300 — 90 of slicing with no part, `conv` 50,
    the `rule` kernel 120, `norm_gate` 40 —; backward region: the core 400 —
    100 of slicing, the rule's forward AGAIN 100, the rule's backward 150,
    `gates` 50 —, the projection's backward 100; Adam 100. With `loop` the
    core's operations run inside a `while` over the rows (the slicing is the
    loop's own time); without, they are the step's own operations."""
    fwd, bwd, again = stacks(kind)
    md = {"%proj.1": meta(f"{fwd}/proj/dot_general:"),
          "%conv.2": meta(f"{fwd}/conv/mul:"),
          "%rule.3": meta(f"{fwd}/rule/pallas_call:", "custom-call"),
          "%ng.4": meta(f"{fwd}/norm_gate/mul:"),
          "%rule.5": meta(f"{again}/rule/pallas_call:", "custom-call"),
          "%rule.6": meta(f"{bwd}/rule/pallas_call:", "custom-call"),
          "%gates.7": meta(f"{bwd}/gates/mul:"),
          "%proj.8": meta(f"{bwd}/proj/dot_general:"),
          "%adam.9": meta("jit(step)/dl4j.update/mul:"),
          "%while.10": meta(f"{fwd}/while:", "while"),
          "%while.11": meta(f"{bwd}/while:", "while"),
          "%slice.12": meta(f"{fwd}/dynamic_slice:"),
          "%slice.13": meta(f"{bwd}/dynamic_update_slice:")}
    ops = []
    for t in (0, 5000):
        ops += [(t, t + 100, "%proj.1"),
                (t + 190, t + 240, "%conv.2"), (t + 240, t + 360, "%rule.3"),
                (t + 360, t + 400, "%ng.4"),
                (t + 500, t + 600, "%rule.5"), (t + 600, t + 750, "%rule.6"),
                (t + 750, t + 800, "%gates.7"),
                (t + 800, t + 900, "%proj.8"), (t + 900, t + 1000, "%adam.9")]
        if loop:
            ops += [(t + 100, t + 400, "%while.10"), (t + 400, t + 800, "%while.11")]
        else:
            ops += [(t + 100, t + 190, "%slice.12"), (t + 400, t + 500, "%slice.13")]
    return sr.account(sorted(ops), "jit_step(7)", [(0, 1000), (5000, 6000)], md, PARTS)


def view(acct, monkeypatch, cfg_name="qwen3-next-80b-a3b-l4", rows=2):
    """A run whose scope account is `acct` (None: nothing to read)."""
    monkeypatch.setattr(sr, "scope_account", lambda run: acct)
    cfg = config(cfg_name)
    return NS(trace=None, window_s=1.0, cell={"name": "x", "chips": 1}, cfg=cfg, traffic={},
              counters={"steps": 2, "rows_per_step": rows, "window_s": 1.0},
              peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
              flops=harness.module("flops", cfg["flops"]))


# ---------------------------------------------------------------------------
# the recurrent cores
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("loop", [True, False], ids=["rows_in_a_while", "no_while"])
@pytest.mark.parametrize("kind", sorted(CORES))
def test_core_share_and_rule_roofline_read_the_same_whatever_holds_the_work(
        kind, loop, monkeypatch):
    cfg_name, share, roofline, flops, bytes_ = CORES[kind]
    run = view(mixer_step(kind, loop), monkeypatch, cfg_name)
    # between the projections: 300 forward + 400 in the backward region of 1000
    assert read(share, run) == pytest.approx(70.0)
    # the rule alone: 120 + 100 again + 150 ns a run
    f = run.flops
    least = max(getattr(f, flops)(run.cfg, 2) / 197e12, getattr(f, bytes_)(run.cfg, 2) / 819e9)
    assert least == getattr(f, bytes_)(run.cfg, 2) / 819e9          # the bytes bound all three
    assert read(roofline, run) == pytest.approx(100 * least * 2 / 740e-9)
    assert read("mixer_rule_share_of_step.train", run) == pytest.approx(37.0)
    # around the rule: conv + norm_gate + gates; the slicing is neither
    assert read("mixer_around_rule_share_of_step.train", run) == pytest.approx(14.0)
    assert read("recompute_share_of_step.train", run) == pytest.approx(10.0)
    assert read("forward_share_of_step.train", run) == pytest.approx(50.0)   # Adam is forward


@pytest.mark.parametrize("kind", sorted(CORES))
def test_another_mixers_readers_find_nothing(kind, monkeypatch):
    run = view(mixer_step(kind, True), monkeypatch, CORES[kind][0])
    for other in sorted(set(CORES) - {kind}):
        assert read(CORES[other][1], run) is None, other
        assert read(CORES[other][2], run) is None, other


@pytest.mark.parametrize("name", sorted({n for c in CORES.values() for n in c[1:3]}
                                        | {"expert_share_of_step.train",
                                           "recompute_share_of_step.train"}))
def test_nothing_to_read_is_none_not_zero(name, monkeypatch):
    assert read(name, view(None, monkeypatch)) is None                  # no account at all
    bare = sr.account([(0, 10, "%adam.9")], "jit_step(7)", [(0, 10)],
                      {"%adam.9": meta("jit(step)/dl4j.update/mul:")}, PARTS)
    assert read(name, view(bare, monkeypatch)) is None                  # a step without the layer


def test_a_roofline_needs_its_configurations_flops(monkeypatch):
    run = view(mixer_step("gateddeltanet", True), monkeypatch, "gpt2-small", rows=8)
    assert read("gdn_roofline.train", run) is None      # flops/gpt2.py counts no delta rule


def test_gdn_least_work_by_hand():
    """Qwen3-Next's three delta layers, 2 rows of 8192 tokens: per token 2 x
    16 x 128 (q, k a KEY head) + 32 x 128 (v) + 2 x 32 (g, beta) floats in,
    32 x 128 out, 32 x 128 x 128 / 64 of state; forward once, backward reads
    all of it and do and writes the inputs' gradients."""
    cfg = config("qwen3-next-80b-a3b-l4")
    f = harness.module("flops", "qwen3_next")
    inputs, out, state = 2 * 2048 + 4096 + 64, 4096, 8192
    per_token = (inputs + out + state) + (inputs + out + state + inputs)
    assert f.gdn_bytes(cfg, 2) == 3 * 2 * 8192 * per_token * 4 == 9701425152
    assert f.gdn_flops(cfg, 2) == f.delta_rule_flops(cfg, 2, 8192)
    assert f.gdn_bytes(cfg, 2) / 819e9 > f.gdn_flops(cfg, 2) / 197e12
    assert not hasattr(f, "delta_scan_bytes") and not hasattr(f, "delta_scan_flops")


# ---------------------------------------------------------------------------
# the experts, the passes, the recompute
# ---------------------------------------------------------------------------
def expert_step():
    """One run of 1000 ns: an expert layer's forward (route 50, gather 50,
    the grouped product 100 — stripped of its stack —, the shared expert 60,
    combine 40), then in the backward region the recomputed activation 30
    and second product 70, combine's backward 50, the product's backward 90,
    the recomputed gather 40 RIGHT IN FRONT of the weight gradient's product
    110 (PR 50's case), the gather's backward 60; the row-blocked head's
    logits 100 and its gradient made in the forward visit 150."""
    fwd, bwd, again = stacks("routedexperts", "L3.sublayerblock")
    md = {"%route.1": meta(f"{fwd}/route/dot_general:"),
          "%gather.2": meta(f"{fwd}/gather/gather:"),
          "%ragged-dot-none.3": meta("ragged-dot-none:", "custom-call"),
          "%shared.4": meta(f"{fwd}/shared/dot_general:"),
          "%combine.5": meta(f"{fwd}/combine/scatter-add:"),
          "%act.6": meta(f"{again}/product/mul:"),
          "%ragged-dot-none.7": meta("ragged-dot-none:", "custom-call"),
          "%combine.8": meta(f"{bwd}/combine/gather:"),
          "%ragged-dot-none.9": meta("ragged-dot-none:", "custom-call"),
          "%gather.10": meta(f"{again}/gather/gather:"),
          "%ragged-dot-none.11": meta("ragged-dot-none:", "custom-call"),
          "%gather.12": meta(f"{bwd}/gather/scatter-add:"),
          "%logits.13": meta("jit(step)/jvp(dl4j.loss)/while/body/dot_general:"),
          "%dz.14": meta("jit(step)/jvp(dl4j.loss)/while/body/grad/dot_general:")}
    call = "bf16[64,8]{1,0} custom-call(s32[4]{0} %gte.1, "
    names = {
        "%ragged-dot-none.3": f"%ragged-dot-none.3 = {call}bf16[64,8]{{1,0}} %gather.2, bf16[4,8,8]{{2,1,0}} %w)",
        "%ragged-dot-none.7": f"%ragged-dot-none.7 = {call}bf16[64,8]{{1,0}} %act.6, bf16[4,8,8]{{2,1,0}} %w)",
        "%ragged-dot-none.9": f"%ragged-dot-none.9 = {call}bf16[64,8]{{1,0}} %combine.8, bf16[4,8,8]{{2,1,0}} %w)",
        "%ragged-dot-none.11": f"%ragged-dot-none.11 = {call}bf16[64,8]{{1,0}} %gather.10, "
                               f"bf16[64,8]{{1,0}} %ragged-dot-none.9)"}
    md = {names.get(k, k): v for k, v in md.items()}
    spans = [("%route.1", 50), ("%gather.2", 50), ("%ragged-dot-none.3", 100), ("%shared.4", 60),
             ("%combine.5", 40), ("%logits.13", 100), ("%dz.14", 150), ("%act.6", 30),
             ("%ragged-dot-none.7", 70), ("%combine.8", 50), ("%ragged-dot-none.9", 90),
             ("%gather.10", 40), ("%ragged-dot-none.11", 110), ("%gather.12", 60)]
    ops, t = [], 0
    for name, ns in spans:
        ops.append((t, t + ns, names.get(name, name)))
        t += ns
    return sr.account(ops, "jit_step(7)", [(0, 1000)], md, PARTS)


def test_a_stripped_product_is_booked_by_what_made_its_operands():
    acct = expert_step()
    ns = {k: [round(v * 1e9) for v in r[:3]] for k, r in acct.rows.items()}
    # forward 100; backward region 70 + 90 + 110, of it recompute 70 alone: the weight
    # gradient's product follows a RECOMPUTED gather and is the backward's all the same
    assert ns[("3", "routedexperts", ("product",))] == [100, 300, 100]
    assert ns[("3", "routedexperts", ("gather",))] == [50, 100, 40]


def test_experts_share_is_every_part_and_the_product_is_its_own(monkeypatch):
    run = view(expert_step(), monkeypatch)
    assert read("expert_share_of_step.train", run) == pytest.approx(75.0)
    assert read("expert_product_share_of_step.train", run) == pytest.approx(40.0)
    assert read("recompute_share_of_step.train", run) == pytest.approx(14.0)


def test_the_loss_gradient_made_in_the_forward_visit_is_the_backward_regions(monkeypatch):
    acct = expert_step()
    ns = {k: [round(v * 1e9) for v in r[:3]] for k, r in acct.rows.items()}
    assert ns[(None, "loss", ())] == [100, 0, 0]
    assert ns[(None, "loss", ("grad",))] == [0, 150, 0]         # no transpose( on its stack
    run = view(acct, monkeypatch)
    assert read("forward_share_of_step.train", run) == pytest.approx(40.0)   # 300 experts + 100
    assert read("head_loss_share_of_step.train", run) == pytest.approx(25.0)
    # the stack itself carries no transpose(: `parse` says so, the account books it backward
    assert not sr.parse("jit(step)/jvp(dl4j.loss)/while/body/grad/dot_general:", PARTS).backward


# ---------------------------------------------------------------------------
# BENCHMARK.json and the files under metrics/
# ---------------------------------------------------------------------------
def bench():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_silent_three_are_gone_and_what_took_their_place_is_listed():
    entries = {m["name"]: m for m in bench()["per_layer"]}
    metrics = os.path.join(harness.HERE, "metrics")
    for name in REMOVED:
        assert name not in entries and not os.path.exists(os.path.join(metrics, name + ".py"))
    for name in ADDED:
        assert name in entries and os.path.exists(os.path.join(metrics, name + ".py"))
    assert entries["gdn_roofline.train"]["workloads"] == ["qwen3next_train_t8192"]
    assert entries["gdn_roofline.train"]["unit"] == "%"
    cells = [w["name"] for w in bench()["workloads"]]
    assert entries["step_wall_median_ms.train"]["workloads"] == cells
    assert entries["step_wall_median_ms.train"]["source"] == "host_clock"
    assert entries["recompute_share_of_step.train"]["workloads"] == [
        c for c in cells if not c.startswith("gpt2s")]            # the GPT-2 steps run no remat


def test_every_entry_has_a_reader_and_no_reader_matches_a_loop_by_its_shape():
    b = bench()
    metrics = os.path.join(harness.HERE, "metrics")
    cells = {w["name"] for w in b["workloads"]}
    end_to_end = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert os.path.exists(os.path.join(metrics, m["name"] + ".py")), m["name"]
        assert set(m["workloads"]) <= cells and m["moves"] in end_to_end
    for name in sorted(os.listdir(metrics)):
        if name.endswith(".py"):
            with open(os.path.join(metrics, name)) as f:
                code = f.read().split('"""', 2)[-1]              # past the docstring
            assert not re.search(r"while", code), name


@pytest.mark.parametrize("cell,has,has_not", [
    ("qwen3next_train_t8192", {"delta_core_share_of_step.train", "gdn_roofline.train",
                               "expert_share_of_step.train"}, ("kda_", "ssd_")),
    ("kimilinear_train_t8192", {"kda_share_of_step.train", "kda_roofline.train",
                                "latent_attention_share_of_step.train"}, ("delta_", "ssd_", "gdn_")),
    ("nemotron3nano_train_t8192", {"ssd_share_of_step.train", "ssd_roofline.train"},
     ("delta_", "kda_", "gdn_")),
    ("kanana2_train_t8192", {"rope_share_of_step.train", "expert_share_of_step.train"},
     ("delta_", "kda_", "ssd_", "gdn_", "mixer_")),
    ("lfm2_train_t8192", {"shortconv_share_of_step.train"}, ("delta_", "kda_", "ssd_", "gdn_")),
    ("ouro_train_t8192_b1", {"loop_share_of_step.train", "exit_entropy.train"},
     ("delta_", "kda_", "ssd_", "gdn_", "expert_")),
    ("laguna_train_t8192_b1", {"window_flash_roofline.train", "window_band_fill.train"},
     ("delta_", "kda_", "ssd_", "gdn_")),
    ("gpt2s_train_t1024_ids", set(), ("delta_", "kda_", "ssd_", "gdn_", "expert_", "recompute_")),
    ("gpt2s_train_t1024", set(), ("delta_", "kda_", "ssd_", "gdn_", "expert_", "recompute_")),
])
def test_a_cell_lists_its_metrics(cell, has, has_not):
    loaded = harness.load_cell(cell)
    names = {m["name"] for m in loaded["per_layer"]}
    everywhere = {"mfu.train", "flash_roofline.train", "flash_share_of_step.train",
                  "device_step_ms.train", "forward_share_of_step.train",
                  "step_wall_median_ms.train", "setup_program_s.train",
                  "compiles_in_window.train", "step_scoped_share.train"}
    assert everywhere | has <= names
    assert not {n for n in names if n.startswith(has_not)}
    assert ("recompute_share_of_step.train" in names) == (not cell.startswith("gpt2s"))
    assert {m["name"] for m in loaded["end_to_end"]} == {"train_throughput", "setup_s"}
    assert loaded["chips"] == 1


# ---------------------------------------------------------------------------
# the hybrid step recorded on a v5e
# ---------------------------------------------------------------------------
@pytest.fixture()
def hybrid(tmp_path, monkeypatch):
    if not os.path.exists(HYBRID):
        pytest.skip("record_scoped_trace.py hybrid has not been run")
    path = tmp_path / "scoped_hybrid_tpu.xplane.pb"
    with gzip.open(HYBRID, "rb") as f:
        path.write_bytes(f.read())
    run = fake_run(tmp_path / "runs", monkeypatch, str(path))
    cfg = config("qwen3-next-80b-a3b-l4")
    run.cfg, run.flops = cfg, harness.module("flops", cfg["flops"])
    run.counters = {"rows_per_step": 2}
    run.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return run


def test_the_recorded_hybrid_step(hybrid):
    """Three steps of a two-layer hybrid on a v5e (3 242 607 ns): the delta
    mixer's rule is the `dl4j_gdn_*` pair — forward, forward again under the
    block's checkpoint, backward: 3 calls a step — with NO `while` around the
    core at this size; two expert layers whose grouped products carry no
    stack; the head's gradient made in its forward visit."""
    acct = sr.scope_account(hybrid)
    assert acct.steps == 3 and round(acct.step_s * 1e9) == 3242607
    ns = {k: [round(v * 1e9) for v in r[:3]] + [r[3]] for k, r in acct.rows.items()}
    assert ns[("1", "gateddeltanet", ("rule",))] == [262903, 487604, 255144, 9]
    assert ns[(None, "loss", ("grad",))] == [0, 79511, 0, 12]          # booked backward
    assert ns[(None, "loss", ())] == [71468, 0, 0, 63]
    # by their operands; the neighbour alone reads 47230 and 46914 ns of recompute here
    assert ns[("1", "routedexperts", ("product",))] == [44633, 106832, 20313, 63]
    assert ns[("2", "routedexperts", ("product",))] == [39106, 105817, 20150, 63]
    share = lambda ns_: pytest.approx(100 * ns_ / 3242607, abs=1e-4)   # noqa: E731
    assert read("delta_core_share_of_step.train", hybrid) == share(1169271)
    assert read("mixer_rule_share_of_step.train", hybrid) == share(750507)
    assert read("expert_share_of_step.train", hybrid) == share(996027)
    assert read("forward_share_of_step.train", hybrid) == share(1044307)
    assert read("recompute_share_of_step.train", hybrid) == share(538013)
    f = hybrid.flops                    # the arithmetic, with Qwen3-Next's least work
    least = max(f.gdn_flops(hybrid.cfg, 2) / 197e12, f.gdn_bytes(hybrid.cfg, 2) / 819e9)
    assert read("gdn_roofline.train", hybrid) == pytest.approx(100 * least * 3 / 750507e-9)
    assert read("kda_share_of_step.train", hybrid) is None and read("ssd_roofline.train", hybrid) is None
    # the rule is kernels, and no `while` of this step is the core's
    kernels = {trace_reduce.family(n) for _, _, n in hybrid.trace.ops[0] if "dl4j_" in n}
    assert {"dl4j_gdn_fwd", "dl4j_gdn_bwd", "dl4j_convsilu_fwd", "dl4j_flash_bwd"} <= kernels
    labels = [k for k, _ in hybrid.trace.device_ops()]
    assert len(labels) == 10 and {k for k in kernels if k.startswith("dl4j_")} <= set(labels)
