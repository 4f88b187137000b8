"""Examples smoke runner — every `examples/*.py` executes green.

The reference's examples repo doubles as its de-facto API regression
surface (dl4j-examples); here the CI suite runs each script end to end in
a subprocess (CPU env, tiny shapes via each script's own CLI) so an API
change that breaks user-facing code fails a test, not a user.
"""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
EXAMPLES = os.path.join(REPO, "examples")

#: script -> (args, timeout_s). Args shrink work to smoke size through
#: each example's own CLI — no special test-only flags.
SCRIPTS = {
    "mnist_lenet.py": (["--epochs", "1", "--batch", "64"], 240),
    "char_rnn.py": (["--epochs", "1", "--seq-len", "20"], 240),
    "computation_graph_multitask.py": (["--epochs", "3"], 240),
    "data_parallel_resnet.py": (
        ["--batch", "8", "--steps", "1", "--image-size", "32"], 420),
    "long_context_ring_attention.py": (
        ["--seq", "256", "--steps", "1"], 300),
    "keras_import.py": ([], 240),
    "dl4j_migration.py": ([], 300),
    "transfer_learning.py": ([], 300),
    "word2vec_embeddings.py": ([], 300),
    "ui_dashboard.py": (["--port", "0", "--epochs", "2"], 240),
    "multihost_training.py": ([], 420),
}


def test_every_example_is_covered():
    """A new example must be added to SCRIPTS (or it silently rots)."""
    on_disk = {f for f in os.listdir(EXAMPLES) if f.endswith(".py")}
    assert on_disk == set(SCRIPTS), (
        f"examples/ and the smoke-runner list diverge: "
        f"only-on-disk={sorted(on_disk - set(SCRIPTS))}, "
        f"only-in-list={sorted(set(SCRIPTS) - on_disk)}")


@pytest.mark.parametrize("script", sorted(SCRIPTS))
def test_example_runs(script):
    args, timeout = SCRIPTS[script]
    env = dict(os.environ)
    # hard-set (not setdefault): one process per chip — a child must run
    # on the CPU backend even if the ambient env points at an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, script), *args],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (
        f"{script} failed (rc={proc.returncode})\n"
        f"--- stdout ---\n{proc.stdout[-3000:]}\n"
        f"--- stderr ---\n{proc.stderr[-3000:]}")
