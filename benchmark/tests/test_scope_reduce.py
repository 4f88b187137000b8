"""The device-scope reader (benchmark/scope_reduce.py): the protobuf wire
decoder against the trace PR 23 recorded on a v5e and against
`xplane_pb2` where that imports; exclusive attribution of a run's time to
the innermost operation, on events built by hand; the scope table of the
small scoped trace `record_scoped_trace.py` recorded on the chip; and every
new reader's `None` where there is nothing to read."""
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import harness, scope_reduce as sr, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(HERE, "small_tpu.xplane.pb")
SCOPED = os.path.join(HERE, "scoped_tpu.xplane.pb")
READERS = ("step_scoped_share.train", "head_loss_share_of_step.train",
           "forward_share_of_step.train", "recompute_share_of_step.train",
           "expert_product_share_of_step.train",
           "mixer_rule_share_of_step.train", "mixer_around_rule_share_of_step.train",
           "expert_share_of_step.train", "delta_core_share_of_step.train",
           "kda_share_of_step.train", "ssd_share_of_step.train", "gdn_roofline.train",
           "kda_roofline.train", "ssd_roofline.train")
PARTS = frozenset({"proj", "rule", "solve", "scan", "conv", "product", "norm"})
# what the recorded scoped trace reads (nanoseconds over its three steps:
# forward, backward region, of it recompute, calls; per cent of the step)
STEP_NS = 1151872
ATTEND_L2 = [24565, 78720, 21992, 36]
LOSS = [132626, 208742, 65133, 162]
SCOPED_SHARE, LOSS_SHARE, FORWARD_SHARE, RECOMPUTE_SHARE = 87.78745, 29.63593, 26.82078, 15.30648


# ---------------------------------------------------------------------------
# the wire decoder
# ---------------------------------------------------------------------------
def test_decoder_reads_what_profile_data_hides():
    md = sr.op_metadata(SMALL)
    [fusion] = md[next(n for n in md if n.startswith("%convert_reduce_fusion"))]
    assert fusion["tf_op"] == "jit(small_step)/dot_general:"
    assert fusion["flops"] == 268959744 and fusion["bytes_accessed"] == 1048580
    assert fusion["source"].endswith("benchmark/tests/record_small_trace.py:23")
    assert fusion["hlo_category"] == "convolution fusion"      # a ref_value, resolved
    # every fusion that ran is an instruction the metadata names
    red = trace_reduce.reduce_file(SMALL, 1)
    ran = {n for _, _, n in red.ops[0]}
    assert {n for n in ran if "fusion" in n.split(" = ")[0]} <= set(md)


def test_decoder_agrees_with_the_generated_proto():
    xplane_pb2 = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    with open(SMALL, "rb") as f:
        space.ParseFromString(f.read())
    plane = next(p for p in space.planes if p.name == "/device:TPU:0")
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    want = {}
    for meta in plane.event_metadata.values():
        stats = {}
        for s in meta.stats:
            kind = s.WhichOneof("value")
            v = getattr(s, kind)
            stats[names[s.metadata_id]] = names[v] if kind == "ref_value" else v
        if "tf_op" in stats:
            want.setdefault(meta.name, []).append(stats)
    got = sr.op_metadata(SMALL)
    assert set(got) == set(want)
    for name, metas in got.items():
        assert all(m in want[name] for m in metas)
    by_id = sr.plane_metadata(memoryview(plane.SerializeToString()))
    assert {k: v[0] for k, v in by_id.items()} == {
        k: v.name for k, v in plane.event_metadata.items()}


def test_decoder_refuses_what_is_no_protobuf():
    with pytest.raises(ValueError):
        list(sr.fields(b"\x0b\x00"))           # wire type 3: a group


# ---------------------------------------------------------------------------
# attribution, on events built by hand
# ---------------------------------------------------------------------------
def meta(tf_op, category="fusion"):
    return [{"tf_op": tf_op, "hlo_category": category, "program_id": 7}]


def test_every_nanosecond_goes_to_the_innermost_operation():
    """Two runs of 1000 ns. A `while` of the forward rule holds two body
    operations (a solve and a scan step); a backward product follows, then
    a recomputed norm; a parameter copy has no scope; 100 ns of each run
    hold no operation."""
    md = {"%while.1": meta("jit(step)/jvp(dl4j.L1.block)/dl4j.delta/rule/while:"),
          "%solve.2": meta("jit(step)/jvp(dl4j.L1.block)/dl4j.delta/rule/while/body/solve/"
                           "triangular_solve:"),
          "%scan.3": meta("jit(step)/jvp(dl4j.L1.block)/dl4j.delta/rule/while/body/scan/mul:"),
          "%dot.4": meta("jit(step)/transpose(jvp(dl4j.L1.block))/dl4j.delta/proj/dot_general:"),
          "%re.5": meta("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
                        "dl4j.L1.block/norm/mul:"),
          "%copy.6": meta("jit(step)/copy:", "data formatting"),
          "%other.9": meta("jit(other)/dl4j.L0.x/proj/dot_general:")}
    ops = []
    for t0 in (0, 5000):
        ops += [(t0, t0 + 500, "%while.1"), (t0 + 100, t0 + 200, "%solve.2"),
                (t0 + 200, t0 + 450, "%scan.3"), (t0 + 500, t0 + 700, "%dot.4"),
                (t0 + 700, t0 + 800, "%re.5"), (t0 + 800, t0 + 900, "%copy.6")]
    ops.append((2000, 3000, "%other.9"))            # another program's: outside the runs
    acct = sr.account(ops, "jit_step(7)", [(0, 1000), (5000, 6000)], md, PARTS)
    ns = {k: [round(v * 1e9) for v in r[:3]] + [r[3]] for k, r in acct.rows.items()}
    assert ns == {
        ("1", "delta", ("rule",)): [300, 0, 0, 2],            # the while's own time
        ("1", "delta", ("rule", "solve")): [200, 0, 0, 2],
        ("1", "delta", ("rule", "scan")): [500, 0, 0, 2],
        ("1", "delta", ("proj",)): [0, 400, 0, 2],
        ("1", "block", ("norm",)): [0, 200, 200, 2],          # recompute, in the backward region
        (None, "unscoped", ()): [200, 0, 0, 2],
        (None, "no operation", ()): [200, 0, 0, 0]}
    assert acct.steps == 2 and round(acct.step_s * 1e9) == 2000
    assert round(acct.scoped_s * 1e9) == 1600 and round(acct.forward_s * 1e9) == 1000
    assert acct.mixers() == {"delta"}
    assert dict(acct.categories) == {"data formatting": pytest.approx(2e-7)}
    table = "\n".join(acct.tables())
    assert "forward 50.00 % + backward region 30.00 % + unscoped and no operation 20.00 %" in table
    assert "delta.rule/solve" in table and table.index("unscoped") > table.index("block.norm")


def test_an_instruction_two_programs_share_resolves_to_the_runs_program():
    md = {"%fusion.1": [{"tf_op": "jit(warm)/mul:", "program_id": 3},
                        {"tf_op": "jit(step)/dl4j.update/mul:", "program_id": 7}]}
    acct = sr.account([(0, 10, "%fusion.1")], "jit_step(7)", [(0, 10)], md, PARTS)
    assert list(acct.rows) == [(None, "update", ()), (None, "no operation", ())]


def test_what_the_compiler_stripped_of_its_stack_adopts_its_neighbours_layer_and_pass():
    """libtpu rewrites `ragged-dot` into a custom call named
    "ragged-dot-none" and nothing else: booked to `routedexperts.product`
    in the layer and pass of the last scoped `routedexperts` operation."""
    md = {"%gather.1": meta("jit(step)/jvp(dl4j.L3.block)/dl4j.routedexperts/gather/gather:"),
          "%mix.2": meta("jit(step)/jvp(dl4j.L3.block)/dl4j.delta/proj/dot_general:"),
          "%ragged-dot-none.7": meta("ragged-dot-none:", "custom-call"),
          "%back.3": meta("jit(step)/transpose(jvp(dl4j.L5.block))/dl4j.routedexperts/combine/mul:"),
          "%ragged-dot-none.9": meta("ragged-dot-none:", "custom-call"),
          "%early.4": meta("ragged-dot-none:", "custom-call")}
    ops = [(0, 10, "%early.4"), (10, 20, "%gather.1"), (20, 30, "%mix.2"),
           (30, 60, "%ragged-dot-none.7"), (60, 70, "%back.3"), (70, 100, "%ragged-dot-none.9")]
    acct = sr.account(ops, "jit_step(7)", [(0, 100)], md, PARTS | {"gather", "combine"})
    ns = {k: [round(v * 1e9) for v in r[:2]] for k, r in acct.rows.items()}
    assert ns[("3", "routedexperts", ("product",))] == [30, 0]
    assert ns[("5", "routedexperts", ("product",))] == [0, 30]
    assert ns[(None, "unscoped", ())] == [10, 0]        # not a grouped product by name: left alone


def test_a_trace_without_tf_op_gives_no_account():
    assert sr.account([(0, 10, "%fusion.1")], "jit_step(7)", [(0, 10)], {}, PARTS) is None
    assert sr.account([], "jit_step(7)", [], {}, PARTS) is None


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
def fake_run(tmp_path, monkeypatch, trace_file=None):
    """A run whose device-only capture is `trace_file` (or absent)."""
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(sr, "_cache", {})
    red = None
    if trace_file:
        d = tmp_path / "cell" / "plugins" / "profile" / "t0"
        d.mkdir(parents=True)
        with open(trace_file, "rb") as f:
            (d / "host.xplane.pb").write_bytes(f.read())
        red = trace_reduce.reduce_file(trace_file, 1)
    return NS(cell={"name": "cell", "chips": 1}, trace=red, flops=None, counters={}, peaks={})


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_without_a_capture(name, tmp_path, monkeypatch):
    assert harness.module("metrics", name).read(fake_run(tmp_path, monkeypatch)) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_for_a_program_without_the_seam(name, tmp_path, monkeypatch):
    monkeypatch.setattr(sr, "program_has_seam", lambda: False)
    run = fake_run(tmp_path, monkeypatch, SMALL)
    assert harness.module("metrics", name).read(run) is None


@pytest.fixture()
def scoped(tmp_path):
    """The trace `record_scoped_trace.py` recorded on a v5e (PR 35), unpacked."""
    import gzip

    path = tmp_path / "scoped_tpu.xplane.pb"
    with gzip.open(SCOPED + ".gz", "rb") as f:
        path.write_bytes(f.read())
    return str(path)


def test_scope_table_of_the_recorded_scoped_step(scoped, tmp_path, monkeypatch, capsys):
    """Three steps of a two-block GPT-2-shaped net, each block a checkpoint,
    flash kernels under `attend`, the head + loss a loop under `dl4j.loss`."""
    run = fake_run(tmp_path / "runs", monkeypatch, scoped)
    acct = sr.scope_account(run)
    assert acct.steps == 3 and round(acct.step_s * 1e9) == STEP_NS
    ns = {k: [round(v * 1e9) for v in r[:3]] + [r[3]] for k, r in acct.rows.items()}
    assert ns[("2", "multiheadattention", ("attend",))] == ATTEND_L2
    assert ns[(None, "loss", ())] == LOSS
    assert ns[(None, "update", ())][1:3] == [0, 0]                   # forward only
    assert {layer for layer, _, _ in acct.rows} == {"0", "1", "2", "3", None}
    kinds = {(kind, parts) for _, kind, parts in acct.rows}
    assert {("transformerblock", ("norm",)), ("transformerblock", ("mlp",)),
            ("multiheadattention", ("proj",)), ("multiheadattention", ("out",)),
            ("embeddingsequence", ()), ("positionembedding", ())} <= kinds
    # the blocks are checkpoints: part of their backward region is the forward again
    block = ns[("2", "transformerblock", ("mlp",))]
    assert 0 < block[2] < block[1] and ns[("2", "multiheadattention", ("attend",))][2] > 0
    # forward + backward region + unscoped + no operation = the step
    total = sum(r[0] + r[1] for r in acct.rows.values())
    assert total == pytest.approx(acct.step_s)
    values = {n: harness.module("metrics", n).read(run) for n in READERS}
    assert values["step_scoped_share.train"] == pytest.approx(SCOPED_SHARE, abs=1e-3)
    assert values["head_loss_share_of_step.train"] == pytest.approx(LOSS_SHARE, abs=1e-3)
    assert values["forward_share_of_step.train"] == pytest.approx(FORWARD_SHARE, abs=1e-3)
    assert values["recompute_share_of_step.train"] == pytest.approx(RECOMPUTE_SHARE, abs=1e-3)
    assert {values[n] for n in READERS[4:]} == {None}               # no experts, no mixer
    assert capsys.readouterr().out.count("device ms a step by dl4j scope") == 2


def test_a_scopeless_trace_reads_zero_scoped_and_says_why(tmp_path, monkeypatch, capsys):
    """The program opens scopes, the executable that ran carried none (the
    trace PR 23 recorded): the stale-cache case."""
    run = fake_run(tmp_path, monkeypatch, SMALL)
    values = {n: harness.module("metrics", n).read(run) for n in READERS}
    assert values.pop("step_scoped_share.train") == 0.0
    assert set(values.values()) == {None}
    out = capsys.readouterr().out
    assert "no dl4j scope in the device trace" in out and "clear the compile cache" in out
    assert out.count("device ms a step by dl4j scope") == 2          # printed once a run
