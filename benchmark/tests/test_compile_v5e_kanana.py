"""The `kanana2_train_t8192` train step compiled at its real size for a
described v5e (as test_compile_v5e_kimi.py does for the other latent-attention
cell): 576 M parameters at 16 B are 9.22 GB, so the step must fit one chip
beside nothing (< 16 GB by `memory_analysis()`) with remat per sub-layer
block and the head + loss in row blocks; it must admit the flash kernels at
keys 192 / values 128, t 8192 in every layer, rotate under a `rope` scope,
and run its experts through XLA's grouped product over a buffer of every
assignment, [gate | up] 1536 wide and the down-projection contracting over
768 as it is (`grouped_width` pads 768 to nothing: 1024 would add a third).

One file, topology inside a module fixture: only the worker that is given
this file loads the TPU library."""
import re

from benchmark.tests.test_compile_v5e import load, step_bytes, topo  # noqa: F401
from benchmark.tests.test_compile_v5e_ids import compile_ids_step


def test_kanana_step_fits_one_chip(topo):  # noqa: F811
    cfg = load("configs", "kanana-2-30b-a3b-l5")
    compiled = compile_ids_step(topo, cfg, load("traffic", "train_ids_t8192_b2"))
    total = step_bytes(compiled)
    m = compiled.memory_analysis()
    print(f"kanana step: {total} bytes; arguments {m.argument_size_in_bytes} "
          f"outputs {m.output_size_in_bytes} aliased {m.alias_size_in_bytes} "
          f"temporaries {m.temp_size_in_bytes}")
    assert 10e9 < total < 16e9, total                    # 10.77 GB: two thirds of the chip
    assert 6.9e9 < m.argument_size_in_bytes < 7.0e9       # weights and Adam's two moments
    text = compiled.as_text()
    assert {"dl4j_flash_fwd", "dl4j_flash_bwd"} == set(
        re.findall(r"dl4j_[a-z]+_[a-z_]*?(?=_(?:bh|n)\d)", text))
    assert "dl4j_flash_fwd_bh64_t8192_d192_dv128" in text and "ragged-dot" in text
    assert "dl4j_flash_bwd_bh64_t8192_d192_dv128" in text
    assert re.search(r"dl4j\.latentattention/rope", text)        # the rotation has a scope
    assert re.search(r"(f32|bf16)\[98304,1536\]", text)          # every assignment a row, [gate | up]
    assert re.search(r"(f32|bf16)\[98304,768\]", text)           # the width as it is, not 1024
    assert not re.search(r"(f32|bf16)\[98304,1024\]", text)
    assert not re.search(r"(f32|bf16)\[16384,16032\]", text)     # the head in row blocks
    assert not re.search(r"f32\[2,32,8192,8192\]", text)         # no materialised scores
    assert not re.search(r"(f32|bf16)\[2,32,8192,32,2\]", text)  # no re-tiling into pairs
