"""The `kimilinear_train_t8192` train step compiled at its real size for a
described v5e (as test_compile_v5e_ids.py does for the other integer-label
cells): 602 M parameters at 16 B are 9.64 GB, so the step must fit one chip
beside nothing (< 16 GB by `memory_analysis()`) with remat per sub-layer
block, the KDA core mapped over rows and the head + loss in row blocks; it
must admit the flash kernels at keys 192 / values 128, t 8192, run its
experts through XLA's grouped product over a buffer of every assignment at
the padded width 2560, and carry the delta rule's state a row at a time.

One file, topology inside a module fixture: only the worker that is given
this file loads the TPU library."""
import re

from benchmark.tests.test_compile_v5e import load, step_bytes, topo  # noqa: F401
from benchmark.tests.test_compile_v5e_ids import compile_ids_step


def test_kimi_step_fits_one_chip(topo):  # noqa: F811
    cfg = load("configs", "kimi-linear-48b-a3b-l5")
    compiled = compile_ids_step(topo, cfg, load("traffic", "train_ids_t8192_b2"))
    total = step_bytes(compiled)
    m = compiled.memory_analysis()
    print(f"kimi step: {total} bytes; arguments {m.argument_size_in_bytes} "
          f"outputs {m.output_size_in_bytes} aliased {m.alias_size_in_bytes} "
          f"temporaries {m.temp_size_in_bytes}")
    assert 11e9 < total < 16e9, total                    # 15 299 121 152 B (PR 51)
    assert 7.2e9 < m.argument_size_in_bytes < 7.3e9       # weights and Adam's two moments
    text = compiled.as_text()
    # flash, the per-channel delta rule's chunks (PR 34), the short convolutions + silu (PR 41)
    assert {"dl4j_flash_fwd", "dl4j_flash_bwd", "dl4j_kda_fwd", "dl4j_kda_bwd",
            "dl4j_convsilu_fwd", "dl4j_convsilu_bwd"} == set(
        re.findall(r"dl4j_[a-z]+_[a-z_]*?(?=_(?:bh|n)\d)", text))
    assert "bh64_t8192_d192_dv128" in text and "ragged-dot" in text
    assert 'ragged_dot_tiling="512,512,512"' in text or "512,512,512" in text
    assert re.search(r"(f32|bf16)\[131072,2560\]", text)         # every assignment a row, padded width
    assert not re.search(r"(f32|bf16)\[16384,20480\]", text)     # the head in row blocks
    assert not re.search(r"f32\[2,32,8192,8192\]", text)         # no materialised scores
    # the KDA core's rows are mapped and the state lives in the kernels' VMEM: what the step
    # holds of it is every chunk's start, a row at a time, as the forward kernel writes it
    assert re.search(r"f32\[128,1,32,128,128\]", text)
    assert not re.search(r"f32\[128,2,32,128,128\]", text)
    assert not re.search(r"(f32|bf16)\[[12],32,128,128\]", text)     # no scan carries it
