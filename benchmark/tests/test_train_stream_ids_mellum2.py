"""`train_stream_ids_mesh` over the tiny `mellum` configuration on FOUR virtual
CPU devices: whole runs (run.py's main, with only the look for a chip skipped)
print `"correct": true` over the sound program — its expert matrices and their
moments split four ways at rest, everything else whole on every device — and
false where one rank's returned rows are left out of the exchange, where a
pair buffer overflows, and with each of the reference's controls in the
program's place.

Run this file on its own (or first): it asks XLA for four host devices before
JAX starts, and skips where JAX already runs with fewer."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax  # noqa: E402
import pytest  # noqa: E402
from jax import lax  # noqa: E402

from benchmark import harness  # noqa: E402
from benchmark.reference import common  # noqa: E402
from benchmark.tests import tiny_mellum2  # noqa: E402
from benchmark.tests.test_correct import SEED, run_main  # noqa: E402
from benchmark.tests.test_train_stream_ids import cell, failed  # noqa: E402
from benchmark.traffic import train_stream_ids as tsi  # noqa: E402
from deeplearning4j_tpu.nn.layers import hybrid  # noqa: E402

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs 4 devices: "
    "XLA_FLAGS=--xla_force_host_platform_device_count=4 before JAX starts")


def mesh_cell(cfg):
    return dict(cell(cfg), chips=4, traffic_params=tiny_mellum2.TRAIN_IDS_MESH)


@pytest.fixture
def four_devices(monkeypatch):
    """The cell's four chips: the first four of however many this process has."""
    devices = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a: devices if not a else jax.local_devices(
        backend=a[0]))


def test_sound_run_is_correct_and_rests_split(monkeypatch, capsys, four_devices):
    seen = {}
    from benchmark.traffic import train_stream as ts
    real = ts.window

    def window(net, *a, **k):
        seen["net"] = net
        return real(net, *a, **k)

    monkeypatch.setattr(ts, "window", window)
    result, out = run_main(monkeypatch, capsys, mesh_cell(tiny_mellum2.mellum2()))
    assert result["correct"] is True, out
    assert set(result["metrics"]) == {"train_throughput", "setup_s"}
    assert "[check] expert_dropped_assignments = 0 limit 0 ok" in out
    net = seen["net"]
    for i in (2, 4, 6, 8):      # the expert blocks: matrices and both moments 4 x [2, ..]
        for tree in (net.params[f"layer_{i}"], net.opt_state[i]["m"], net.opt_state[i]["v"]):
            for name in ("Wgu", "Wd"):
                leaf = tree["sub"][name]
                assert {s.data.shape[0] for s in leaf.addressable_shards} == {2}, (i, name)
            assert tree["sub"]["router"].addressable_shards[0].data.shape == (32, 8)


def broken(monkeypatch, capsys, cfg=None):
    result, out = run_main(monkeypatch, capsys, mesh_cell(cfg or tiny_mellum2.mellum2()))
    assert result["correct"] is False, out
    print("\n".join(l for l in out.splitlines() if l.startswith("[check]")))
    return failed(out)


def test_one_ranks_returned_rows_left_out_is_not_correct(monkeypatch, capsys, four_devices):
    real = lax.all_to_all
    calls = []

    def all_to_all(x, axis_name, split_axis, concat_axis, **kw):
        out = real(x, axis_name, split_axis, concat_axis, **kw)
        calls.append(x.shape)
        if x.dtype != "int32" and len(calls) % 3 == 0:      # out, ids, BACK: rank 1's rows lost
            out = out.at[1].set(0)
        return out

    monkeypatch.setattr(hybrid.lax, "all_to_all", all_to_all)
    assert any("gap" in name for name in broken(monkeypatch, capsys))
    assert calls


def test_an_overflowing_pair_buffer_is_not_correct(monkeypatch, capsys, four_devices):
    # (a tiny pair's rows round up to 128, past every assignment: cut them by hand)
    monkeypatch.setattr(hybrid.RoutedExperts, "pair_rows", lambda self, rows, ranks: 8)
    assert "expert_dropped_assignments" in broken(monkeypatch, capsys)


def numbers(cfg, operand=None):
    ref = harness.module("reference", cfg["reference"])
    batches = tsi.make_batches(cfg, tiny_mellum2.TRAIN_IDS_MESH, 4, SEED)
    p0 = jax.device_get(ref.init_params(cfg, SEED))
    return ref, tsi.reference_numbers(ref, cfg, p0, {}, batches, 3, operand)


@pytest.fixture(scope="module")
def want():
    return numbers(tiny_mellum2.mellum2())


@pytest.mark.parametrize("control", ["float8_e4m3fn", "drop_rank_back", "window_short",
                                     "drop_yarn"])
def test_the_controls_come_out_not_correct(control, want):
    ref, sound = want
    assert control == ref.CONTROL or control in ref.CONTROLS
    _, ctl = numbers(tiny_mellum2.mellum2(), control)
    rows = common.compare_training(ctl, sound, ref.LIMITS, ref.COMPARISONS)
    assert not all(r[3] for r in rows), rows
    same = common.compare_training(sound, sound, ref.LIMITS, ref.COMPARISONS)
    assert all(r[3] for r in same)
