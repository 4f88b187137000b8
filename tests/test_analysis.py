"""Static-analysis subsystem: the config-time model graph analyzer
(analysis/graph.py, rule IDs DLA001..DLA012 — one deliberately-broken
config per rule), the runtime jit-seam donation audit (DLA013,
analysis/donation.py), the jaxlint AST purity linter
(analysis/jaxlint.py, JX001..JX012 — including the SELF-HOSTING gate
over the package tree), and the satellites that ride with them
(util.envflags normalization, util.cotangent float0 zeros, the
chunked-LSTM auto-admission bound)."""
import os
import warnings
from dataclasses import dataclass
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.analysis import analyze
from deeplearning4j_tpu.analysis import jaxlint
from deeplearning4j_tpu.nn import inputs as it
from deeplearning4j_tpu.nn.conf import (
    MultiLayerConfiguration,
    NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.graph_vertices import MergeVertex
from deeplearning4j_tpu.nn.layers import LSTM, Dense, LossLayer, Output
from deeplearning4j_tpu.util import envflags


def _rules(rep, severity=None):
    ds = rep.diagnostics if severity is None else rep.by_severity(severity)
    return {d.rule for d in ds}


def _mlc(layers, input_type=it.feed_forward(16)):
    c = NeuralNetConfiguration().list(layers)
    if input_type is not None:
        c.set_input_type(input_type)
    return c


# ===========================================================================
# graph analyzer — one broken config per rule ID
# ===========================================================================


class TestAnalyzerRules:
    def test_dla001_no_layers(self):
        rep = analyze(NeuralNetConfiguration().list([]))
        assert "DLA001" in _rules(rep, "error")
        with pytest.raises(ValueError, match="no layers"):
            NeuralNetConfiguration().list([]).validate()

    def test_dla001_graph_missing_inputs_outputs(self):
        g = NeuralNetConfiguration().graph()
        rep = analyze(g)
        assert "DLA001" in _rules(rep, "error")
        g2 = (NeuralNetConfiguration().graph().add_inputs("in")
              .add_layer("d", Dense(n_out=4), "in"))
        assert "DLA001" in _rules(analyze(g2), "error")  # no outputs

    def test_dla002_dangling_reference(self):
        g = (NeuralNetConfiguration().graph()
             .add_inputs("in")
             .add_layer("d", Dense(n_out=4), "ghost")
             .set_outputs("d")
             .set_input_types(it.feed_forward(8)))
        rep = analyze(g)
        errs = [d for d in rep.errors if d.rule == "DLA002"]
        assert errs and "'ghost' undefined" in errs[0].message
        g.set_outputs("nope")
        assert any(d.rule == "DLA002" and "not a vertex" in d.message
                   for d in analyze(g).errors)
        # hand-edited wiring: a vertex_inputs key naming no vertex is a
        # diagnostic, not a KeyError (untrusted-JSON contract)
        g3 = (NeuralNetConfiguration().graph()
              .add_inputs("in")
              .add_layer("d", Dense(n_out=4), "in")
              .set_outputs("d")
              .set_input_types(it.feed_forward(8)))
        g3.vertex_inputs["ghost"] = ["in"]
        assert any(d.rule == "DLA002" and "names no vertex" in d.message
                   for d in analyze(g3).errors)

    def test_dla003_cycle(self):
        g = (NeuralNetConfiguration().graph().add_inputs("in"))
        g.vertices["a"] = MergeVertex()
        g.vertex_inputs["a"] = ["in", "b"]
        g.vertices["b"] = MergeVertex()
        g.vertex_inputs["b"] = ["a"]
        g.set_outputs("b").set_input_types(it.feed_forward(4))
        rep = analyze(g)
        assert "DLA003" in _rules(rep, "error")
        with pytest.raises(ValueError, match="cycle"):
            g.validate()

    def test_dla004_unreachable(self):
        g = (NeuralNetConfiguration().graph()
             .add_inputs("in", "unused")
             .add_layer("d", Dense(n_out=4), "in")
             .add_layer("dead", Dense(n_out=4), "in")
             .add_layer("out", Output(n_out=3), "d")
             .set_outputs("out")
             .set_input_types(it.feed_forward(8), it.feed_forward(8)))
        rep = analyze(g)
        warns = [d for d in rep.warnings if d.rule == "DLA004"]
        assert {"dead", "unused"} <= {d.location for d in warns}
        # an OUTPUT that data can never reach is an error, not a warning
        g.vertices["island"] = MergeVertex()
        g.vertex_inputs["island"] = []
        g.set_outputs("out", "island")
        assert any(d.rule == "DLA004" and d.severity == "error"
                   for d in analyze(g).diagnostics)

    def test_dla005_shape_mismatches(self):
        # declared n_in disagrees with the propagated input width
        rep = analyze(_mlc([Dense(n_in=32, n_out=4)]))
        assert "DLA005" in _rules(rep, "error")
        # no input_type and no n_in on the first layer
        rep = analyze(_mlc([Dense(n_out=4)], input_type=None))
        assert any(d.rule == "DLA005" and "No input_type" in d.message
                   for d in rep.errors)
        # graph: LayerVertex is single-input but wired to two
        g = (NeuralNetConfiguration().graph()
             .add_inputs("a", "b")
             .add_layer("d", Dense(n_out=4), "a", "b")
             .set_outputs("d")
             .set_input_types(it.feed_forward(4), it.feed_forward(4)))
        assert any(d.rule == "DLA005" and "takes 1 input" in d.message
                   for d in analyze(g).errors)
        # graph: input_types count mismatch
        g2 = (NeuralNetConfiguration().graph()
              .add_inputs("a", "b")
              .add_layer("d", Dense(n_out=4), "a")
              .add_layer("e", Dense(n_out=4), "b")
              .set_outputs("d", "e")
              .set_input_types(it.feed_forward(4)))
        assert any(d.rule == "DLA005" and "input types" in d.message
                   for d in analyze(g2).errors)

    def test_dla006_loss_activation_mismatch(self):
        cases = [
            (Output(n_out=4, loss="mse", activation="softmax"), "mse"),
            (Output(n_out=4, loss="mcxent", activation="sigmoid"), "mcxent"),
            (Output(n_out=4, loss="xent", activation="softmax"), "xent"),
            (LossLayer(loss="mcxent"), "mcxent"),  # identity default
        ]
        for layer, loss in cases:
            rep = analyze(_mlc([Dense(n_out=4), layer]))
            hits = [d for d in rep.warnings if d.rule == "DLA006"]
            assert hits and loss in hits[0].message, (loss, rep.summary())
        # the canonical pairings stay silent
        ok = analyze(_mlc([Output(n_out=4, loss="mcxent")]))
        assert "DLA006" not in _rules(ok)

    def test_dla007_bad_width(self):
        rep = analyze(_mlc([Dense(n_out=0)]))
        assert "DLA007" in _rules(rep, "error")
        rep = analyze(_mlc([Output(n_out=-3)]))
        assert "DLA007" in _rules(rep, "error")

    def test_dla008_memory_info(self):
        rep = analyze(_mlc([Dense(n_out=8), Output(n_out=2)]),
                      batch=16)
        infos = [d for d in rep.infos if d.rule == "DLA008"]
        # 16*8+8 + 8*2+2 = 154 params, counted without allocating any
        assert infos and "154 params" in infos[0].message

    def test_dla009_hbm_budget(self):
        rep = analyze(_mlc([Dense(n_out=512), Output(n_out=10)],
                           input_type=it.feed_forward(512)),
                      hbm_gib=0.0001)
        assert "DLA009" in _rules(rep, "warning")
        assert "DLA009" not in _rules(analyze(_mlc([Output(n_out=2)])))

    def test_dla010_partition_spec_rank(self):
        @dataclass
        class BadSpecDense(Dense):
            def tensor_partition_specs(self, params, model_axis="model",
                                       model_size=1):
                from jax.sharding import PartitionSpec as P

                # W is rank 2 — a 3-dim spec can never apply; b [10] does
                # not divide model_size=4
                return {"W": P(None, None, model_axis), "b": P(model_axis)}

        conf = _mlc([BadSpecDense(n_out=10)])
        rep = analyze(conf, model_size=4)
        msgs = [d.message for d in rep.warnings if d.rule == "DLA010"]
        assert any("names 3 dims" in m for m in msgs)
        assert any("not divisible by" in m for m in msgs)
        # rank checks are a sharded-config concern: silent at model_size=1
        assert "DLA010" not in _rules(analyze(conf))

    def test_dla011_no_loss_terminal(self):
        rep = analyze(_mlc([Dense(n_out=4)]))
        assert "DLA011" in _rules(rep, "warning")
        g = (NeuralNetConfiguration().graph()
             .add_inputs("in")
             .add_layer("d", Dense(n_out=4), "in")
             .set_outputs("d")
             .set_input_types(it.feed_forward(8)))
        assert "DLA011" in _rules(analyze(g), "warning")

    def test_dla012_softmax_width_one(self):
        rep = analyze(_mlc([Output(n_out=1, loss="mcxent")]))
        assert "DLA012" in _rules(rep, "warning")

    def test_validate_seam_emits_warnings(self):
        conf = _mlc([Dense(n_out=8),
                     Output(n_out=4, loss="mse", activation="softmax")])
        with pytest.warns(UserWarning, match="DLA006"):
            conf.build()

    def test_rule_id_floor(self):
        """The acceptance floor: >= 8 distinct rule IDs are live."""
        all_rules = set()
        for conf, kw in [
            (NeuralNetConfiguration().list([]), {}),
            (_mlc([Dense(n_in=32, n_out=0),
                   Output(n_out=1, loss="mse", activation="softmax")]),
             {"hbm_gib": 0.00001}),
            (_mlc([Dense(n_out=4)]), {}),
        ]:
            all_rules |= _rules(analyze(conf, **kw))
        g = (NeuralNetConfiguration().graph()
             .add_inputs("in", "unused")
             .add_layer("d", Dense(n_out=4), "ghost")
             .set_outputs("d")
             .set_input_types(it.feed_forward(4), it.feed_forward(4)))
        all_rules |= _rules(analyze(g))
        assert len(all_rules) >= 8, sorted(all_rules)


class TestAnalyzerSweeps:
    def test_all_zoo_configs_analyze_clean(self):
        """Every zoo architecture: zero errors AND zero warnings."""
        from tests.test_zoo import ALL_MODELS

        for cls in ALL_MODELS:
            rep = analyze(cls().conf())
            assert rep.ok, f"{cls.__name__}: {rep.summary()}"
            assert not rep.warnings, f"{cls.__name__}: {rep.summary()}"
            assert any(d.rule == "DLA008" for d in rep.infos)

    def test_recurrent_and_preprocessor_propagation(self):
        """Shape propagation crosses preprocessors and RNN layers."""
        conf = (NeuralNetConfiguration()
                .list([LSTM(n_out=12),
                       Output(n_out=3, loss="mcxent")])
                .set_input_type(it.recurrent(5, 20)))
        assert analyze(conf).ok

    def test_cli_analyze(self, tmp_path, capsys):
        from deeplearning4j_tpu.cli import main
        from deeplearning4j_tpu.zoo import LeNet

        p = tmp_path / "lenet.json"
        p.write_text(LeNet().conf().to_json())
        assert main(["analyze", "--conf", str(p)]) == 0
        assert "DLA008" in capsys.readouterr().out
        bad = _mlc([Dense(n_in=32, n_out=4)])
        p2 = tmp_path / "bad.json"
        p2.write_text(bad.to_json())
        assert main(["analyze", "--conf", str(p2), "--json"]) == 1
        assert "DLA005" in capsys.readouterr().out


# ===========================================================================
# jaxlint
# ===========================================================================


def _lint(src, path="deeplearning4j_tpu/somemod.py"):
    return jaxlint.lint_source(src, path)


class TestJaxlintRules:
    def test_jx001_raw_env_gate(self):
        # the gate names use parse-time string concat so a repo-wide grep
        # for raw reads doesn't hit these lint FIXTURES; jaxlint parses
        # the fixture source, where they are single Constant nodes
        src = ('import os\n'
               'def gate():\n'
               '    return os.environ.get("DL4J_TPU" "_FOO") == "1"\n'
               'def sub():\n'
               '    return os.environ["DL4J_TPU" "_BAR"]\n')
        rules = [d.rule for d in _lint(src)]
        assert rules == ["JX001", "JX001"]
        # exempt inside the helper itself; writes are not reads
        assert not _lint(src, "deeplearning4j_tpu/util/envflags.py")
        assert not _lint('import os\n'
                         'os.environ["DL4J_TPU_BAR"] = "1"\n')
        # non-gate env vars are out of scope
        assert not _lint('import os\n'
                         'def f():\n'
                         '    return os.environ.get("HOME")\n')

    def test_jx002_defvjp_zeros_like_cotangent(self):
        src = ('import jax\n'
               'import jax.numpy as jnp\n'
               '@jax.custom_vjp\n'
               'def f(x, labels):\n'
               '    return x\n'
               'def _fwd(x, labels):\n'
               '    return x, labels\n'
               'def _bwd(res, g):\n'
               '    return g, jnp.zeros_like(res)\n'
               'f.defvjp(_fwd, _bwd)\n')
        assert [d.rule for d in _lint(src)] == ["JX002"]
        fixed = src.replace(
            "jnp.zeros_like(res)",
            "zeros_cotangent(res)").replace(
            "import jax.numpy as jnp",
            "import jax.numpy as jnp\n"
            "from deeplearning4j_tpu.util.cotangent import zeros_cotangent")
        assert not _lint(fixed)
        # zeros_like OUTSIDE a registered bwd is not a cotangent
        assert not _lint('import jax.numpy as jnp\n'
                         'def g(x):\n'
                         '    return jnp.zeros_like(x)\n')

    def test_jx003_import_time_jax_compute(self):
        assert [d.rule for d in _lint(
            'import jax.numpy as jnp\nTABLE = jnp.arange(128)\n'
        )] == ["JX003"]
        # default-arg expressions evaluate at import too
        assert [d.rule for d in _lint(
            'import jax.numpy as jnp\n'
            'def f(x=jnp.zeros(3)):\n'
            '    return x\n')] == ["JX003"]
        # function bodies, wrapper-building and dtype attributes are fine
        assert not _lint(
            'import functools\n'
            'import jax\n'
            'import jax.numpy as jnp\n'
            'PARAM = jnp.float32\n'
            '@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))\n'
            'def f(x, n):\n'
            '    return jnp.zeros(n)\n')

    def test_jx004_python_rng_in_traced_dirs(self):
        src = ('import numpy as np\n'
               'import random\n'
               'def sample(x):\n'
               '    return x * np.random.rand() + random.random()\n')
        rules = [d.rule for d in _lint(src, "deeplearning4j_tpu/ops/k.py")]
        assert rules == ["JX004", "JX004"]
        assert _lint(src, "deeplearning4j_tpu/nn/layers/d.py")
        # outside traced dirs (host-side code) Python RNG is legitimate
        assert not _lint(src, "deeplearning4j_tpu/ui/server.py")
        # jax.random is the traced-safe way and stays silent
        assert not _lint('import jax\n'
                         'def sample(x, key):\n'
                         '    return x * jax.random.uniform(key)\n',
                         "deeplearning4j_tpu/ops/k.py")

    def test_jx005_traced_branch(self):
        src = ('import jax.numpy as jnp\n'
               'def f(x):\n'
               '    if jnp.any(x > 0):\n'
               '        return x\n'
               '    return -x\n')
        assert [d.rule for d in _lint(src, "deeplearning4j_tpu/ops/k.py")] \
            == ["JX005"]
        # static shape/dtype queries are Python values under tracing
        assert not _lint('import jax.numpy as jnp\n'
                         'def f(x):\n'
                         '    if jnp.ndim(x) > 2 and x.dtype == jnp.float32:\n'
                         '        return x\n'
                         '    return -x\n',
                         "deeplearning4j_tpu/ops/k.py")

    def test_suppressions(self):
        src = ('import jax.numpy as jnp\n'
               'T = jnp.arange(4)  # jaxlint: disable=JX003\n')
        assert not _lint(src)
        src_file = ('# jaxlint: disable-file=JX003\n'
                    'import jax.numpy as jnp\n'
                    'A = jnp.arange(4)\n'
                    'B = jnp.arange(8)\n')
        assert not _lint(src_file)
        # suppressing one rule does not hide another
        src_other = ('import jax.numpy as jnp\n'
                     'T = jnp.arange(4)  # jaxlint: disable=JX001\n')
        assert [d.rule for d in _lint(src_other)] == ["JX003"]
        # bare disable-file suppresses every rule (mirrors bare disable)
        assert not _lint('# jaxlint: disable-file\n'
                         'import jax.numpy as jnp\n'
                         'A = jnp.arange(4)\n')
        # a pragma on ANY physical line of a multi-line statement works
        assert not _lint('import jax.numpy as jnp\n'
                         'T = jnp.arange(\n'
                         '    128)  # jaxlint: disable=JX003\n')

    def test_jx003_lambda_defaults(self):
        """Lambda default-arg expressions execute at import time too."""
        assert [d.rule for d in _lint(
            'import jax.numpy as jnp\n'
            'f = lambda x=jnp.zeros(3): x\n')] == ["JX003"]
        assert not _lint('import jax.numpy as jnp\n'
                         'f = lambda x: jnp.zeros(3)\n')

    def test_jx006_raw_model_checkpoint_writes(self):
        # raw binary writes to model/checkpoint-looking paths: torn on
        # crash — must route through resilience.checkpoint's atomic writer
        assert [d.rule for d in _lint(
            'def save(b):\n'
            '    with open("bestModel.zip", "wb") as f:\n'
            '        f.write(b)\n')] == ["JX006"]
        assert [d.rule for d in _lint(
            'import numpy as np\n'
            'def save(ckpt_path, arrays):\n'
            '    np.savez(ckpt_path, **arrays)\n')] == ["JX006"]
        assert [d.rule for d in _lint(
            'import zipfile\n'
            'def save(model_path):\n'
            '    return zipfile.ZipFile(model_path, mode="w")\n'
        )] == ["JX006"]
        # generic paths, reads, and text-mode writes are out of scope
        assert not _lint('def save(path, b):\n'
                         '    with open(path, "wb") as f:\n'
                         '        f.write(b)\n')
        assert not _lint('import zipfile\n'
                         'def load(model_path):\n'
                         '    return zipfile.ZipFile(model_path)\n')
        assert not _lint('def save(manifest, s):\n'
                         '    with open("model.json", "w") as f:\n'
                         '        f.write(s)\n')
        # the atomic writer and the serializer it wraps are exempt
        assert not _lint(
            'def save(b):\n'
            '    open("model.zip.tmp", "wb").write(b)\n',
            "deeplearning4j_tpu/resilience/checkpoint.py")
        assert not _lint(
            'import zipfile\n'
            'def write_model(net, model_path):\n'
            '    return zipfile.ZipFile(model_path, "w")\n',
            "deeplearning4j_tpu/models/serialization.py")

    def test_jx007_wall_clock_durations(self):
        # direct subtraction of time.time() calls
        assert [d.rule for d in _lint(
            'import time\n'
            'def f(t0):\n'
            '    return time.time() - t0\n')] == ["JX007"]
        # cross-statement: a name assigned from time.time() subtracted
        # later (the TimeIterationListener defect shape — assignment in
        # __init__, subtraction in a callback)
        assert [d.rule for d in _lint(
            'import time\n'
            'class L:\n'
            '    def __init__(self):\n'
            '        self.start = time.time()\n'
            '    def eta(self):\n'
            '        return time.time() - self.start\n')] == ["JX007"]
        assert [d.rule for d in _lint(
            'import time\n'
            'def f():\n'
            '    t0 = time.time()\n'
            '    work()\n'
            '    return t0 - 1.0\n')] == ["JX007"]
        # pure timestamps (never subtracted) and monotonic clocks are fine
        assert not _lint('import time\n'
                         'def stamp():\n'
                         '    return {"time": time.time()}\n')
        assert not _lint('import time\n'
                         'def f(t0):\n'
                         '    return time.perf_counter() - t0\n')
        # anchored-wall derivation (distributed/stats.py idiom): time.time
        # is read once and only ever ADDED to — no subtraction, no finding
        assert not _lint('import time\n'
                         '_WALL = time.time()\n'
                         '_PERF = time.perf_counter()\n'
                         'def now():\n'
                         '    return _WALL + (time.perf_counter() - _PERF)\n')
        # allowlisting a legitimate wall-difference site via pragma
        assert not _lint(
            'import time\n'
            'def age(file_mtime):\n'
            '    return time.time() - file_mtime'
            '  # jaxlint: disable=JX007\n')

    def test_jx008_jit_in_loop(self):
        # a wrapper created per loop iteration recompiles every time
        src = ('import jax\n'
               'def sweep(fns, x):\n'
               '    for f in fns:\n'
               '        g = jax.jit(f)\n'
               '        x = g(x)\n'
               '    return x\n')
        assert [d.rule for d in _lint(src)] == ["JX008"]
        # while-loops and functools.partial(jax.jit, ...) count too
        src_partial = ('import jax\n'
                       'import functools\n'
                       'def f(x):\n'
                       '    while x.cond:\n'
                       '        s = functools.partial(jax.jit,'
                       ' static_argnums=1)(x.fn)\n'
                       '        x = s(x, 1)\n'
                       '    return x\n')
        assert [d.rule for d in _lint(src_partial)] == ["JX008"]
        # a decorated function DEFINED inside a loop rebuilds its wrapper
        # per iteration
        src_deco = ('import jax\n'
                    'def f(items):\n'
                    '    for it_ in items:\n'
                    '        @jax.jit\n'
                    '        def step(x):\n'
                    '            return x + it_\n'
                    '        step(1.0)\n')
        assert [d.rule for d in _lint(src_deco)] == ["JX008"]

    def test_jx008_immediate_invocation(self):
        # jax.jit(f)(x): wrapper + cache discarded after one call
        src = ('import jax\n'
               'def grad_of(f, x):\n'
               '    return jax.jit(jax.grad(f))(x)\n')
        assert [d.rule for d in _lint(src)] == ["JX008"]
        # pragma allowlists deliberate one-shot sites (gradientcheck)
        assert not _lint('import jax\n'
                         'def g(f, x):\n'
                         '    return jax.jit(f)(x)'
                         '  # jaxlint: disable=JX008\n')

    def test_jx008_clean_patterns(self):
        # module-level / function-body wrappers bound once are the
        # SUPPORTED idiom — including the jaxcompat.jit seam, and a
        # nested function whose BODY jits (runs at call time, not per
        # loop iteration)
        assert not _lint(
            'import jax\n'
            'from deeplearning4j_tpu.util import jaxcompat\n'
            '@jax.jit\n'
            'def top(x):\n'
            '    return x\n'
            'def build():\n'
            '    step = jaxcompat.jit(lambda x: x, watch_name="s")\n'
            '    return step\n'
            'def outer(items):\n'
            '    for i in items:\n'
            '        def make():\n'
            '            return jax.jit(lambda x: x + 1)\n'
            '        use(make)\n')

    def test_jx009_silent_swallow(self):
        # an except handler whose whole body is `pass` loses the traceback
        src = ('def f():\n'
               '    try:\n'
               '        g()\n'
               '    except Exception:\n'
               '        pass\n')
        assert [d.rule for d in _lint(src)] == ["JX009"]
        # bare except: pass counts too
        src_bare = ('def f():\n'
                    '    try:\n'
                    '        g()\n'
                    '    except:\n'
                    '        pass\n')
        assert [d.rule for d in _lint(src_bare)] == ["JX009"]

    def test_jx009_clean_and_pragma(self):
        # logging, re-raising, or any real handling is fine
        assert not _lint('import logging\n'
                         'def f():\n'
                         '    try:\n'
                         '        g()\n'
                         '    except Exception:\n'
                         '        logging.exception("g failed")\n')
        assert not _lint('def f():\n'
                         '    try:\n'
                         '        g()\n'
                         '    except ValueError:\n'
                         '        raise\n')
        # pragma'd best-effort teardown sites are allowlisted
        assert not _lint('def f():\n'
                         '    try:\n'
                         '        g()\n'
                         '    except OSError:\n'
                         '        pass  # jaxlint: disable=JX009 — teardown\n')

    def test_jx010_host_sync_in_hot_loop(self):
        # the per-step score-fetch shape: a device->host sync every
        # iteration of a hot-loop-dir (models/parallel/training/
        # distributed) For/While body
        src = ('import numpy as np\n'
               'def fit(it_, step):\n'
               '    for ds in it_:\n'
               '        score = step(ds)\n'
               '        s = float(score)\n'
               '        a = np.asarray(score)\n'
               '        score.block_until_ready()\n'
               '        b = score.item()\n')
        rules = [d.rule for d in _lint(
            src, "deeplearning4j_tpu/models/mod.py")]
        assert rules == ["JX010"] * 4

    def test_jx010_scoped_to_hot_dirs_and_loops(self):
        src = ('def fit(it_, step):\n'
               '    for ds in it_:\n'
               '        s = float(step(ds))\n')  # composite arg: passes
        assert not _lint(src, "deeplearning4j_tpu/models/mod.py")
        sync = ('def fit(it_, step):\n'
                '    for ds in it_:\n'
                '        score = step(ds)\n'
                '        s = float(score)\n')
        # same sync outside the hot-loop dirs: not JX010's business
        assert not _lint(sync, "deeplearning4j_tpu/telemetry/mod.py")
        # outside any loop: a one-shot fetch is a boundary, not a tax
        assert not _lint('def f(score):\n'
                         '    return float(score)\n',
                         "deeplearning4j_tpu/models/mod.py")
        assert [d.rule for d in _lint(
            sync, "deeplearning4j_tpu/parallel/mod.py")] == ["JX010"]

    def test_jx010_function_body_resets_loop_context(self):
        # a helper DEFINED in a loop runs at call time — its body is not
        # per-iteration host traffic
        src = ('def fit(it_):\n'
               '    for ds in it_:\n'
               '        def report(score):\n'
               '            return float(score)\n'
               '        use(report)\n')
        assert not _lint(src, "deeplearning4j_tpu/models/mod.py")

    def test_jx010_pragma(self):
        src = ('def fit(it_, step):\n'
               '    for ds in it_:\n'
               '        score = step(ds)\n'
               '        s = float(score)  '
               '# jaxlint: disable=JX010 — tbptt chunk boundary\n')
        assert not _lint(src, "deeplearning4j_tpu/models/mod.py")

    def test_jx011_unbounded_wait(self):
        # a zero-argument join()/get() in cluster-facing dirs blocks
        # forever on an evicted worker — the coordinator must never
        # inherit a lost peer's hang
        src = ('def drain(t, q):\n'
               '    t.join()\n'
               '    return q.get()\n')
        rules = [d.rule for d in _lint(
            src, "deeplearning4j_tpu/distributed/mod.py")]
        assert rules == ["JX011"] * 2
        assert [d.rule for d in _lint(
            src, "deeplearning4j_tpu/parallel/mod.py")] == ["JX011"] * 2
        assert [d.rule for d in _lint(
            src, "deeplearning4j_tpu/resilience/mod.py")] == ["JX011"] * 2

    def test_jx011_bounded_or_out_of_scope(self):
        # timeouts (positional or keyword) are the fix, str.join/dict.get
        # always take arguments, and other dirs are out of scope
        bounded = ('def drain(t, q, d):\n'
                   '    t.join(0.02)\n'
                   '    q.get(timeout=5)\n'
                   '    ",".join(d)\n'
                   '    d.get("k")\n')
        assert not _lint(bounded, "deeplearning4j_tpu/distributed/mod.py")
        src = ('def drain(t):\n'
               '    t.join()\n')
        assert not _lint(src, "deeplearning4j_tpu/telemetry/mod.py")
        # reasoned infinite waits carry the pragma
        assert not _lint(
            'def drain(q):\n'
            '    return q.get()  '
            '# jaxlint: disable=JX011 — sentinel-bounded consumer idle\n',
            "deeplearning4j_tpu/distributed/mod.py")

    def test_jx012_unbounded_event_wait(self):
        # a zero-argument Event/Condition .wait() parks the caller until
        # someone calls set()/notify() — and in serving-facing code that
        # someone can be a crashed dispatcher (the shutdown-hang bug this
        # rule is the static twin of, parallel/inference.py PR 8)
        src = ('def await_result(req):\n'
               '    req.event.wait()\n')
        for d in ("parallel", "serving", "distributed"):
            assert [x.rule for x in _lint(
                src, f"deeplearning4j_tpu/{d}/mod.py")] == ["JX012"]

    def test_jx012_bounded_or_out_of_scope(self):
        # any argument (positional or keyword timeout) bounds the wait;
        # module-level functions that merely spell `.wait` (os.wait)
        # resolve through the alias map and are skipped; other dirs are
        # out of scope
        bounded = ('import os\n'
                   'def await_result(req, cv):\n'
                   '    req.event.wait(0.05)\n'
                   '    cv.wait(timeout=1.0)\n'
                   '    os.wait()\n')
        assert not _lint(bounded, "deeplearning4j_tpu/serving/mod.py")
        src = ('def await_result(req):\n'
               '    req.event.wait()\n')
        assert not _lint(src, "deeplearning4j_tpu/telemetry/mod.py")
        # reasoned infinite waits carry the pragma
        assert not _lint(
            'def await_result(req):\n'
            '    req.event.wait()  '
            '# jaxlint: disable=JX012 — resolver is exception-safe\n',
            "deeplearning4j_tpu/serving/mod.py")

    def test_jx011_covers_serving_dir(self):
        # the serving queue/dispatcher joined the JX011 scope with PR 8
        src = ('def drain(t, q):\n'
               '    t.join()\n'
               '    return q.get()\n')
        assert [d.rule for d in _lint(
            src, "deeplearning4j_tpu/serving/mod.py")] == ["JX011"] * 2

    def test_jx013_manual_span_open(self):
        # a span held in a variable and entered by hand can miss its
        # finish on an exception path — and with PR 10 the __enter__
        # also attaches a TraceContext that only __exit__ detaches, so
        # the leak corrupts every later span on the thread
        src = ('def step(tr):\n'
               '    sp = tr.span("fit")\n'
               '    sp.__enter__()\n')
        assert [d.rule for d in _lint(
            src, "deeplearning4j_tpu/training/mod.py")] == ["JX013"]
        # bare-statement opens are just as leaked
        assert [d.rule for d in _lint(
            'def step(tr):\n    tr.start_span("fit")\n')] == ["JX013"]

    def test_jx013_managed_forms_and_pragma(self):
        # the three managed shapes: with-item, enter_context argument,
        # and a return value (the caller manages); thread.start() never
        # matches (the rule keys on span/start_span, not bare start)
        good = ('import threading\n'
                'def step(tr, stack):\n'
                '    with tr.span("fit"):\n'
                '        pass\n'
                '    stack.enter_context(tr.span("epoch"))\n'
                '    t = threading.Thread(target=step)\n'
                '    t.start()\n'
                'def opener(tr):\n'
                '    return tr.span("fit")\n')
        assert not _lint(good, "deeplearning4j_tpu/training/mod.py")
        # reasoned manual sites carry the pragma
        assert not _lint(
            'def probe(tr):\n'
            '    sp = tr.span("x")  '
            '# jaxlint: disable=JX013 — finished in finally below\n',
            "deeplearning4j_tpu/telemetry/mod.py")

    def test_jx014_sleep_retry_loop(self):
        # the hand-rolled shed-retry loop submit_with_retry replaces:
        # catch, sleep a constant, go again — a fleet of these
        # re-stampedes in sync the moment capacity returns
        src = ('import time\n'
               'def call(server, x):\n'
               '    for _ in range(5):\n'
               '        try:\n'
               '            return server.output(x)\n'
               '        except Exception:\n'
               '            time.sleep(0.1)\n')
        assert [d.rule for d in _lint(
            src, "deeplearning4j_tpu/serving/mod.py")] == ["JX014"]
        # while-loops are the same shape; distributed/ is in scope too
        assert [d.rule for d in _lint(
            src.replace("for _ in range(5):", "while True:"),
            "deeplearning4j_tpu/distributed/mod.py")] == ["JX014"]

    def test_jx014_blessed_backoff_and_scope(self):
        # a loop that derives its delay from decorrelated_backoff IS the
        # blessed shape (resilience/retry.py jitters it)
        good = ('import time\n'
                'def call(server, x):\n'
                '    d = 0.05\n'
                '    for _ in range(5):\n'
                '        try:\n'
                '            return server.output(x)\n'
                '        except Exception:\n'
                '            d = decorrelated_backoff(d, 0.05, 5.0)\n'
                '            time.sleep(d)\n')
        assert not _lint(good, "deeplearning4j_tpu/serving/mod.py")
        flagged = ('import time\n'
                   'def poll(q):\n'
                   '    while True:\n'
                   '        try:\n'
                   '            return q.pop()\n'
                   '        except Exception:\n'
                   '            time.sleep(1.0)\n')
        # out-of-scope dirs and the backoff module itself never match
        assert not _lint(flagged, "deeplearning4j_tpu/training/mod.py")
        assert not _lint(flagged, "deeplearning4j_tpu/resilience/retry.py")
        # a sleeping loop WITHOUT an except handler is pacing, not retry
        pacing = ('import time\n'
                  'def pace():\n'
                  '    for _ in range(3):\n'
                  '        time.sleep(0.1)\n')
        assert not _lint(pacing, "deeplearning4j_tpu/serving/mod.py")
        # reasoned fixed-cadence sites carry the pragma
        assert not _lint(
            flagged.replace(
                "time.sleep(1.0)",
                "time.sleep(1.0)  "
                "# jaxlint: disable=JX014 — fixed cadence by design"),
            "deeplearning4j_tpu/resilience/mod.py")

    def test_jx016_literal_coordinator_check(self):
        # the hand-rolled coordinator test runtime_info().is_coordinator
        # replaces; both orders of the comparison are the same smell
        src = ('import jax\n'
               'def save(model):\n'
               '    if jax.process_index() == 0:\n'
               '        model.save("out.zip")\n')
        assert [d.rule for d in _lint(
            src, "deeplearning4j_tpu/training/mod.py")] == ["JX016"]
        assert [d.rule for d in _lint(
            src.replace("jax.process_index() == 0",
                        "0 != jax.process_index()"),
            "deeplearning4j_tpu/serving/mod.py")] == ["JX016"]

    def test_jx016_definition_site_nonliteral_and_pragma(self):
        src = ('import jax\n'
               'def save(model):\n'
               '    if jax.process_index() == 0:\n'
               '        model.save("out.zip")\n')
        # runtime.py DEFINES the coordinator role: the literal check is
        # the definition, not a fork of it
        assert not _lint(
            src, "deeplearning4j_tpu/distributed/runtime.py")
        # comparing against a non-literal (an elected/config rank) passes
        assert not _lint(
            src.replace("== 0", "== coordinator_rank"),
            "deeplearning4j_tpu/training/mod.py")
        # process_index compared to something non-int is not a role check
        assert not _lint(
            src.replace("== 0", '== "zero"'),
            "deeplearning4j_tpu/training/mod.py")
        # reasoned literal checks carry the pragma
        assert not _lint(
            src.replace(
                "== 0:",
                "== 0:  # jaxlint: disable=JX016 — bench-only rank probe"),
            "deeplearning4j_tpu/training/mod.py")

    def test_jx017_anonymous_runtime_thread(self):
        src = ('import threading\n'
               'def start(fn):\n'
               '    t = threading.Thread(target=fn)\n'
               '    t.start()\n')
        # in a runtime dir, missing name= AND daemon= is one finding
        # naming both missing pieces
        findings = _lint(src, "deeplearning4j_tpu/serving/mod.py")
        assert [d.rule for d in findings] == ["JX017"]
        assert "name=" in findings[0].message
        assert "daemon=True" in findings[0].message
        # daemon present but anonymous still fires (trace lanes)
        named_less = src.replace("target=fn", "target=fn, daemon=True")
        assert [d.rule for d in _lint(
            named_less, "deeplearning4j_tpu/telemetry/mod.py")] == ["JX017"]
        # explicit daemon=False is a choice the pragma must own
        assert [d.rule for d in _lint(
            src.replace("target=fn", 'target=fn, name="x", daemon=False'),
            "deeplearning4j_tpu/distributed/mod.py")] == ["JX017"]

    def test_jx017_satisfied_scoped_and_pragma(self):
        full = ('import threading\n'
                'def start(fn, flag):\n'
                '    threading.Thread(target=fn, daemon=True,\n'
                '                     name="dl4j-tpu-lane").start()\n')
        assert not _lint(full, "deeplearning4j_tpu/parallel/mod.py")
        # a non-constant daemon= value is a runtime decision — passes
        assert not _lint(
            full.replace("daemon=True", "daemon=flag"),
            "deeplearning4j_tpu/parallel/mod.py")
        bare = ('import threading\n'
                'def start(fn):\n'
                '    threading.Thread(target=fn).start()\n')
        # outside the runtime dirs the rule is out of scope
        assert not _lint(bare, "deeplearning4j_tpu/ui/mod.py")
        # lifecycle-managed threads carry the reasoned pragma
        assert not _lint(
            bare.replace(
                ".start()",
                ".start()  # jaxlint: disable=JX017 — joined before exit"),
            "deeplearning4j_tpu/resilience/mod.py")

    def test_jx020_unbounded_buffer_on_runtime_path(self):
        src = ('import queue\n'
               'import collections\n'
               'def build():\n'
               '    q = queue.Queue()\n'
               '    d = collections.deque()\n'
               '    return q, d\n')
        assert [d.rule for d in _lint(
            src, "deeplearning4j_tpu/serving/mod.py")] == ["JX020", "JX020"]
        assert [d.rule for d in _lint(
            src, "deeplearning4j_tpu/distributed/mod.py")] == [
                "JX020", "JX020"]
        # from-imports resolve the same ctors
        frm = ('from queue import LifoQueue\n'
               'from collections import deque\n'
               'S = LifoQueue()\n'
               'D = deque()\n')
        assert [d.rule for d in _lint(
            frm, "deeplearning4j_tpu/telemetry/mod.py")] == [
                "JX020", "JX020"]

    def test_jx020_bounded_scoped_and_pragma(self):
        bounded = ('import queue\n'
                   'import collections\n'
                   'Q = queue.Queue(maxsize=64)\n'
                   'P = queue.PriorityQueue(maxsize=8)\n'
                   'D = collections.deque(maxlen=16)\n'
                   'E = collections.deque(range(4), 4)\n')
        assert not _lint(bounded, "deeplearning4j_tpu/serving/mod.py")
        # outside the runtime dirs the rule is out of scope
        loose = 'import queue\nQ = queue.Queue()\n'
        assert not _lint(loose, "deeplearning4j_tpu/ui/mod.py")
        assert not _lint(loose, "deeplearning4j_tpu/training/mod.py")
        # a buffer bounded elsewhere carries the reasoned pragma
        assert not _lint(
            loose.replace(
                "Queue()",
                "Queue()  # jaxlint: disable=JX020 — capped by admission"),
            "deeplearning4j_tpu/serving/mod.py")

    def test_self_hosting_tree_is_clean(self):
        """Tier-1 gate: jaxlint over the package tree must stay clean —
        the same invocation as `python -m deeplearning4j_tpu.analysis.jaxlint`."""
        rep = jaxlint.lint_paths()
        assert not rep.diagnostics, rep.summary()

    def test_unparseable_source_degrades_to_jx000(self):
        """Untokenizable/unparseable files become a diagnostic, not a
        linter crash (unterminated bracket kills both tokenize and ast)."""
        findings = _lint("def f(:\n")
        assert [d.rule for d in findings] == ["JX000"]

    def test_main_exit_codes(self, tmp_path, capsys):
        bad = tmp_path / "deeplearning4j_tpu_mod.py"
        bad.write_text('import jax.numpy as jnp\nT = jnp.arange(3)\n')
        assert jaxlint.main([str(bad)]) == 1
        assert "JX003" in capsys.readouterr().out
        good = tmp_path / "ok.py"
        good.write_text('X = 1\n')
        assert jaxlint.main([str(good)]) == 0


# ===========================================================================
# satellites
# ===========================================================================


class TestEnvFlags:
    def test_spelling_contract(self):
        for spelling in ("1", "true", "YES", " on ", "True"):
            with mock.patch.dict(os.environ, {"DL4J_TPU_T": spelling}):
                assert envflags.flag("DL4J_TPU_T") is True
        for spelling in ("0", "false", "no", "off", "", " 0 ", "garbage"):
            with mock.patch.dict(os.environ, {"DL4J_TPU_T": spelling}):
                assert envflags.flag("DL4J_TPU_T") is False
        with mock.patch.dict(os.environ, clear=True):
            assert envflags.flag("DL4J_TPU_T") is None
            assert envflags.enabled("DL4J_TPU_T", default=True) is True
            assert envflags.mode("DL4J_TPU_T") == "auto"
        with mock.patch.dict(os.environ, {"DL4J_TPU_T": "on"}):
            assert envflags.mode("DL4J_TPU_T") == "forced"
        with mock.patch.dict(os.environ, {"DL4J_TPU_T": "whatever"}):
            assert envflags.mode("DL4J_TPU_T") == "off"
        with mock.patch.dict(os.environ, {"DL4J_TPU_T": "  x  "}):
            assert envflags.value("DL4J_TPU_T") == "x"

    def test_xent_gate_normalized(self):
        """ADVICE r5: 'False', 'no', ' 0 ' must now DISABLE the xent
        helper (they used to count as enabled)."""
        from deeplearning4j_tpu.ops import xent_kernel as xk

        for spelling in ("False", "no", " 0 ", "off"):
            with mock.patch.dict(os.environ,
                                 {"DL4J_TPU_PALLAS_XENT": spelling}):
                assert xk.xent_helper_enabled() is False
        with mock.patch.dict(os.environ, {"DL4J_TPU_PALLAS_XENT": "1"}):
            assert xk.xent_helper_enabled() is True


class TestCotangent:
    def test_zeros_cotangent_dtypes(self):
        from deeplearning4j_tpu.util.cotangent import zeros_cotangent

        f = zeros_cotangent(jnp.ones((3, 2), jnp.float32))
        assert f.dtype == jnp.float32 and not np.asarray(f).any()
        z = zeros_cotangent(jnp.ones((3, 2), jnp.int32))
        assert z.dtype == jax.dtypes.float0 and z.shape == (3, 2)
        b = zeros_cotangent(jnp.ones((4,), bool))
        assert b.dtype == jax.dtypes.float0


class TestChunkedLstmAdmission:
    def test_auto_regime_bounds(self):
        """ADVICE r5: auto-admission stays in the measured b=8/n=256
        neighborhood — small batch, wide cell, long f32 sequences."""
        from deeplearning4j_tpu.ops.pallas_kernels import (
            chunked_lstm_auto_regime,
        )

        assert chunked_lstm_auto_regime(8, 1024, 256, jnp.float32)
        assert chunked_lstm_auto_regime(8, 4096, 256, jnp.float32)
        assert chunked_lstm_auto_regime(16, 2048, 128, jnp.float32)
        # out of regime: short t, large batch, narrow cell, bf16
        assert not chunked_lstm_auto_regime(8, 512, 256, jnp.float32)
        assert not chunked_lstm_auto_regime(64, 4096, 256, jnp.float32)
        assert not chunked_lstm_auto_regime(8, 4096, 64, jnp.float32)
        assert not chunked_lstm_auto_regime(8, 4096, 256, jnp.bfloat16)


# ===========================================================================
# DLA013 — jit-seam donation + precision audit (analysis/donation.py)
# ===========================================================================


class TestDonationAudit:
    def _net(self):
        from deeplearning4j_tpu.models import MultiLayerNetwork
        from deeplearning4j_tpu.nn import updaters

        conf = NeuralNetConfiguration(
            seed=5, updater=updaters.Adam(learning_rate=1e-3),
        ).list([
            Dense(n_out=8, activation="relu"),
            Output(n_out=3, loss="mcxent"),
        ]).set_input_type(it.feed_forward(4))
        return MultiLayerNetwork(conf).init()

    def _fit_once(self, net):
        from deeplearning4j_tpu.datasets.dataset import DataSet

        rng = np.random.default_rng(0)
        x = rng.standard_normal((6, 4)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 6)]
        net.fit(DataSet(x, y), epochs=1)

    def test_unbuilt_seams_recorded_not_warned(self):
        from deeplearning4j_tpu.analysis import audit_model

        rep = audit_model(self._net())  # fit() builds seams lazily
        assert "DLA013" not in _rules(rep, "warning")
        seams = rep.estimates["donation"]["seams"]
        assert seams["train_step"] == {"built": False}

    def test_donating_train_seam_is_clean(self):
        from deeplearning4j_tpu.analysis import audit_model

        net = self._net()
        self._fit_once(net)
        rep = audit_model(net)
        assert "DLA013" not in _rules(rep, "warning")
        entry = rep.estimates["donation"]["seams"]["train_step"]
        assert entry["built"] and entry["params_donated"]
        assert entry["opt_state_donated"]
        assert entry["undonated_bytes"] == 0
        assert rep.estimates["donation"]["param_bytes"] > 0

    def test_undonated_train_seam_warns_with_bytes(self):
        from deeplearning4j_tpu.analysis import audit_model

        class Stub:
            pass

        stub = Stub()
        stub.params = [{"W": np.zeros((8, 8), np.float32)}]
        stub.opt_state = [{"m": np.zeros((8, 8), np.float32)}]

        def seam(*a):
            raise AssertionError("audit must not call the seam")

        seam.__donate_argnums__ = (1,)  # state only: params/opt missing
        seam.__watch_name__ = "Stub.train_step"
        stub._train_step = seam
        rep = audit_model(stub)
        warns = [d for d in rep.by_severity("warning")
                 if d.rule == "DLA013"]
        assert len(warns) == 1 and "second live copy" in warns[0].message
        entry = rep.estimates["donation"]["seams"]["train_step"]
        assert not entry["params_donated"]
        assert not entry["opt_state_donated"]
        assert entry["undonated_bytes"] == 2 * 8 * 8 * 4

    def test_f32_masters_under_bf16_policy_surface_info(self):
        from deeplearning4j_tpu import dtypes
        from deeplearning4j_tpu.analysis import audit_model

        net = self._net()
        self._fit_once(net)
        assert not [d for d in audit_model(net).diagnostics
                    if d.severity == "info" and d.rule == "DLA013"]
        dtypes.set_mixed_precision(True)
        try:
            infos = [d for d in audit_model(net).diagnostics
                     if d.severity == "info" and d.rule == "DLA013"]
        finally:
            dtypes.set_mixed_precision(False)
        assert len(infos) == 1
        assert "f32 master parameters" in infos[0].message
