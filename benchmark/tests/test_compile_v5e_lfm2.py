"""The `lfm2_train_t8192` train step compiled at its real size for a
described v5e (as test_compile_v5e_kanana.py does for its cell): 788 M
parameters at 16 B are 12.61 GB, so the step must fit one chip beside nothing
(< 15.4 GB by `memory_analysis()`: ISSUE 40's line for holding 16 experts a
layer and not 8) with remat per sub-layer block and the head + loss in row
blocks; it must admit the flash kernels at head 64, t 8192 (32 query heads
over 8 key/value heads), open the short-convolution mixer's four parts and
the attention layer's rotation under `gates`, and run its experts through
XLA's grouped product over a buffer of every assignment, [gate | up] 3072
wide and the down-projection contracting over 1536, with no shared expert.

One file, topology inside a module fixture: only the worker that is given
this file loads the TPU library."""
import re

from benchmark.tests.test_compile_v5e import load, step_bytes, topo  # noqa: F401
from benchmark.tests.test_compile_v5e_ids import compile_ids_step


def test_lfm2_step_fits_one_chip(topo):  # noqa: F811
    cfg = load("configs", "lfm2-24b-a2b-l5")
    compiled = compile_ids_step(topo, cfg, load("traffic", "train_ids_t8192_b2"))
    total = step_bytes(compiled)
    m = compiled.memory_analysis()
    print(f"lfm2 step: {total} bytes; arguments {m.argument_size_in_bytes} "
          f"outputs {m.output_size_in_bytes} aliased {m.alias_size_in_bytes} "
          f"temporaries {m.temp_size_in_bytes}")
    assert 12.6e9 < total < 15.4e9, total
    assert 9.4e9 < m.argument_size_in_bytes < 9.5e9       # weights and Adam's two moments
    text = compiled.as_text()
    assert {"dl4j_flash_fwd", "dl4j_flash_bwd"} == set(
        re.findall(r"dl4j_[a-z]+_[a-z_]*?(?=_(?:bh|n)\d)", text))
    assert "dl4j_flash_fwd_bh64_t8192_d64" in text and "ragged-dot" in text
    assert "dl4j_flash_bwd_bh64_t8192_d64" in text
    for part in ("proj", "gates", "conv", "out"):                # the new mixer's four parts
        assert re.search(rf"dl4j\.gatedshortconv/{part}", text), part
    assert re.search(r"dl4j\.gatedattention/gates", text)        # the heads' norms and rotation
    assert not re.search(r"routedexperts/shared", text)          # no shared expert
    assert re.search(r"(f32|bf16)\[65536,3072\]", text)          # every assignment a row, [gate | up]
    assert re.search(r"(f32|bf16)\[65536,1536\]", text)
    assert not re.search(r"(f32|bf16)\[16384,8192\]", text)      # the head in row blocks
    assert not re.search(r"f32\[2,32,8192,8192\]", text)         # no materialised scores
