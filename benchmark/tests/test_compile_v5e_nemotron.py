"""The `nemotron3nano_train_t8192` train step compiled at its real size for a
described v5e (as test_compile_v5e_ids.py does for the other integer-label
cells): 667 M parameters at 16 B are 10.67 GB, so the step must fit one chip
beside nothing (< 16 GB by `memory_analysis()`) with remat per block, the
state-space core mapped over rows and the head + loss in row blocks; it must
admit the flash kernels at head 128, t 8192, run its experts through XLA's
grouped product over a buffer of every assignment, and carry the state-space
recurrence's state a row at a time.

One file, topology inside a module fixture: only the worker that is given
this file loads the TPU library."""
import re

from benchmark.tests.test_compile_v5e import load, step_bytes, topo  # noqa: F401
from benchmark.tests.test_compile_v5e_ids import compile_ids_step


def test_pattern_step_fits_one_chip(topo):  # noqa: F811
    cfg = load("configs", "nemotron-3-nano-30b-a3b-l9")
    compiled = compile_ids_step(topo, cfg, load("traffic", "train_ids_t8192_b2"))
    total = step_bytes(compiled)
    m = compiled.memory_analysis()
    print(f"pattern step: {total} bytes; arguments {m.argument_size_in_bytes} "
          f"outputs {m.output_size_in_bytes} aliased {m.alias_size_in_bytes} "
          f"temporaries {m.temp_size_in_bytes}")
    # 15 323 799 040 B (PR 51; no `h` kept: a layer's 384 MiB are over `hybrid.H_KEEP_BYTES`).
    # The upper bound stays the chip's 16e9: ROADMAP S10 (f) waits on this line — with the
    # bound at 384 MiB the step holds 16 087 123 456 B and fails here, and raising it takes
    # paired runs of this cell first
    assert 11e9 < total < 16e9, total
    text = compiled.as_text()
    # flash, the short convolution + silu (PR 41), the state-space rule's chunks (PR 42)
    assert {"dl4j_flash_fwd", "dl4j_flash_bwd", "dl4j_convsilu_fwd", "dl4j_convsilu_bwd",
            "dl4j_ssd_fwd", "dl4j_ssd_bwd"} == set(
        re.findall(r"dl4j_[a-z]+_[a-z_]*?(?=_(?:bh|n)\d)", text))
    assert "bh64_t8192_d128" in text and "ragged-dot" in text
    assert re.search(r"(f32|bf16)\[98304,2688\]", text)          # every assignment a row
    assert not re.search(r"(f32|bf16)\[16384,16384\]", text)     # the head in row blocks
    # the state-space core's rows are mapped and a group's states live in the kernels' VMEM: what
    # the step holds of them is every chunk's start, a row at a time (8 groups of 8 heads x 64)
    assert re.search(r"f32\[64,1,8,512,128\]", text)
    assert not re.search(r"f32\[64,2,8,512,128\]", text)
    assert not re.search(r"(f32|bf16)\[[12],64,64,128\]", text)      # no scan carries it
