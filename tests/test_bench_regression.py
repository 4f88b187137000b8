"""bench.py --check-regression (ISSUE 10 satellite): the CI tripwire
comparing two bench artifacts. Synthetic fixtures pin the exit-code
contract — a 10% throughput drop fails, noise passes, lower-is-better
rows (p99/shed) gate in the opposite direction, rows present in only
one file never gate. Pure-JSON path: importing bench never imports
jax."""
import json
import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import bench  # noqa: E402


def _write(tmp_path, name, doc):
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


def _wrapper(value, metric="resnet50_images_per_sec_per_chip"):
    """The driver-wrapper artifact shape (BENCH_r0x.json)."""
    return {"n": 1, "cmd": "python bench.py", "rc": 0, "tail": "",
            "parsed": {"model": "resnet50", "metric": metric,
                       "value": value}}


def _detail(qps, p99_ms, shed):
    """The BENCH_DETAIL.json shape with a serving sweep row."""
    return {"_note": "synthetic", "serving": {
        "metric": "serving_sustained_qps", "value": qps,
        "sweep": [
            {"offered_x": 1.0, "latency_p99_ms": p99_ms / 2,
             "shed_rate": 0.0},
            {"offered_x": 2.0, "latency_p99_ms": p99_ms,
             "shed_rate": shed},
        ]}}


class TestCheckRegression:
    def test_ten_percent_throughput_drop_fails(self, tmp_path, capsys):
        old = _write(tmp_path, "old.json", _wrapper(2600.0))
        new = _write(tmp_path, "new.json", _wrapper(2340.0))  # -10%
        assert bench.check_regression(old, new) == 1
        out = capsys.readouterr().out
        assert "REGRESSED" in out and "-10.0%" in out
        assert "1 regressed" in out

    def test_noise_passes(self, tmp_path, capsys):
        old = _write(tmp_path, "old.json", _wrapper(2623.0))
        new = _write(tmp_path, "new.json", _wrapper(2600.0))  # -0.9%
        assert bench.check_regression(old, new) == 0
        out = capsys.readouterr().out
        assert "ok" in out and "0 regressed" in out

    def test_throughput_gain_never_fails(self, tmp_path):
        old = _write(tmp_path, "old.json", _wrapper(2600.0))
        new = _write(tmp_path, "new.json", _wrapper(5200.0))
        assert bench.check_regression(old, new) == 0

    def test_lower_is_better_rows_gate_upward(self, tmp_path, capsys):
        old = _write(tmp_path, "old.json", _detail(900.0, 40.0, 0.10))
        # qps flat, but 2x-overload p99 +50% and shed doubled
        new = _write(tmp_path, "new.json", _detail(900.0, 60.0, 0.20))
        assert bench.check_regression(old, new) == 1
        out = capsys.readouterr().out
        assert "serving_sustained_qps.2x.latency_p99_ms" in out
        assert out.count("REGRESSED") == 2
        # and an IMPROVEMENT in those rows passes
        better = _write(tmp_path, "better.json",
                        _detail(900.0, 20.0, 0.01))
        assert bench.check_regression(old, better) == 0

    def test_zero_floor_rate_uses_absolute_delta(self, tmp_path, capsys):
        old = _write(tmp_path, "old.json", _detail(900.0, 40.0, 0.0))
        new = _write(tmp_path, "new.json", _detail(900.0, 40.0, 0.2))
        assert bench.check_regression(old, new) == 1
        assert "+0.2" in capsys.readouterr().out

    def test_threshold_is_tunable(self, tmp_path):
        old = _write(tmp_path, "old.json", _wrapper(2600.0))
        new = _write(tmp_path, "new.json", _wrapper(2340.0))
        assert bench.check_regression(old, new, threshold=0.15) == 0

    def test_one_only_rows_listed_never_gate(self, tmp_path, capsys):
        old_doc = _detail(900.0, 40.0, 0.1)
        old_doc["resnet50"] = {"metric": "resnet50_images_per_sec",
                               "value": 2600.0}
        old = _write(tmp_path, "old.json", old_doc)
        new = _write(tmp_path, "new.json", _detail(900.0, 41.0, 0.1))
        assert bench.check_regression(old, new) == 0
        out = capsys.readouterr().out
        assert "old only" in out and "resnet50_images_per_sec" in out

    def test_unreadable_or_disjoint_inputs_exit_2(self, tmp_path, capsys):
        good = _write(tmp_path, "good.json", _wrapper(1.0))
        assert bench.check_regression(
            str(tmp_path / "missing.json"), good) == 2
        torn = tmp_path / "torn.json"
        torn.write_text("{not json")
        assert bench.check_regression(str(torn), good) == 2
        empty = _write(tmp_path, "empty.json", {"tail": "no rows here"})
        assert bench.check_regression(empty, good) == 2
        other = _write(tmp_path, "other.json",
                       _wrapper(1.0, metric="different_metric"))
        assert bench.check_regression(other, good) == 2
        errs = capsys.readouterr().err
        assert "unreadable" in errs and "no comparable rows" in errs
        assert "share no rows" in errs

    def test_importing_bench_does_not_import_jax(self):
        """The regression gate must run before (and without) jax — it is
        a pure-JSON comparison usable on any CI box."""
        import subprocess

        code = ("import sys; import bench; "
                "sys.exit(1 if 'jax' in sys.modules else 0)")
        assert subprocess.run(
            [sys.executable, "-c", code], cwd=_ROOT).returncode == 0
