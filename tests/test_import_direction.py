"""The arrows between the packages point one way: nn -> ops, parallel -> ops,
parallel -> nn; what `ops/` and `nn/` both need (the `REMAT_KEEP` tag) stands
in `util/`. A layer reaches a kernel through its family's entry function
in `ops/` (`ops.attention.attend`, `ops.fused_lstm`, `ops.fused_affine_act`,
`ops.fused_linear_xent`, `ops.delta.kda_chunks` / `gdn_chunks`), never through the kernel
modules or `parallel/`."""
import ast
import pathlib

import pytest

import deeplearning4j_tpu

ROOT = pathlib.Path(deeplearning4j_tpu.__file__).parent


def imported_modules(path):
    """Every dotted name an import statement of `path` could bind:
    `import a.b`, `from a import b` (a and a.b), at any depth of the file."""
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module and not node.level:
            yield node.module
            yield from (f"{node.module}.{a.name}" for a in node.names)


def imports_of(package, forbidden, at_least):
    """`file: module` for every import of `forbidden`, or of a module under
    it, in the files of `package` (more than `at_least` of them)."""
    files = sorted((ROOT / package).rglob("*.py"))
    assert len(files) > at_least
    return [f"{f.relative_to(ROOT)}: {m}" for f in files
            for m in imported_modules(f)
            if m == forbidden or m.startswith(forbidden + ".")]


@pytest.mark.parametrize("forbidden", [
    "deeplearning4j_tpu.parallel",
    "deeplearning4j_tpu.ops.pallas_kernels",
    "deeplearning4j_tpu.ops.xent_kernel",
    "deeplearning4j_tpu.ops.kda_kernels",
    "deeplearning4j_tpu.ops.gdn_kernels",
    "deeplearning4j_tpu.ops.chunk_kernels",
])
def test_nn_does_not_import(forbidden):
    assert not imports_of("nn", forbidden, at_least=20)


@pytest.mark.parametrize("forbidden", ["deeplearning4j_tpu.nn", "deeplearning4j_tpu.parallel"])
def test_ops_does_not_import(forbidden):
    assert not imports_of("ops", forbidden, at_least=8)


def test_one_remat_keep_tag():
    """`nn/` and `parallel/` read the tag off `base`, `ops/` off `util/`: one string."""
    from deeplearning4j_tpu.nn.layers import base
    from deeplearning4j_tpu.ops import pallas_kernels
    from deeplearning4j_tpu.util import jaxcompat

    assert base.REMAT_KEEP is jaxcompat.REMAT_KEEP is pallas_kernels.REMAT_KEEP


@pytest.mark.parametrize("module,forbidden", [
    ("gdn_kernels", "kda_kernels"), ("kda_kernels", "gdn_kernels"),
    ("chunk_kernels", "kda_kernels"), ("chunk_kernels", "gdn_kernels"),
])
def test_the_delta_rules_kernels_share_the_skeleton_and_not_each_other(module, forbidden):
    """`ops/chunk_kernels.py` is what both rules' kernel pairs are built on;
    neither rule imports the other's arithmetic, nor the skeleton a rule's."""
    found = [m for m in imported_modules(ROOT / "ops" / f"{module}.py") if forbidden in m]
    assert not found, found
