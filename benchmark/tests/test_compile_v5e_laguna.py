"""The `laguna_train_t8192_b1` train step compiled at its real size for a
described v5e (as test_compile_v5e_lfm2.py does for its cell): 672 M
parameters at 16 B are 10.75 GB, so the step must fit one chip (< 15.4 GB by
`memory_analysis()`: ISSUE 47's line for holding 1 of 2 head ranks and not 1
of 4) with remat per sub-layer block and the head + loss in row blocks; it
must hold a flash kernel pair WITH the window in its name for the three
sliding layers (36 heads) and one WITHOUT for the two global ones (24 heads),
split and turn the heads of both kinds in the rope kernel pair (a turned part
of 128 and of 64 of 128), gate a head under `gates`, and run its experts
through XLA's grouped product over a buffer of every assignment beside the
shared expert.

One file, topology inside a module fixture: only the worker that is given
this file loads the TPU library."""
import re

from benchmark.tests.test_compile_v5e import load, step_bytes, topo  # noqa: F401
from benchmark.tests.test_compile_v5e_ids import compile_ids_step


def test_laguna_step_fits_one_chip(topo):  # noqa: F811
    cfg = load("configs", "laguna-s-2.1-l5")
    compiled = compile_ids_step(topo, cfg, load("traffic", "train_ids_t8192_b1"))
    total = step_bytes(compiled)
    m = compiled.memory_analysis()
    print(f"laguna step: {total} bytes; arguments {m.argument_size_in_bytes} "
          f"outputs {m.output_size_in_bytes} aliased {m.alias_size_in_bytes} "
          f"temporaries {m.temp_size_in_bytes}")
    assert 0.25 * 16e9 < 10.7e9 < total < 15.4e9, total
    assert 8.0e9 < m.argument_size_in_bytes < 8.2e9       # weights and Adam's two moments
    text = compiled.as_text()
    assert {"dl4j_flash_fwd", "dl4j_flash_bwd", "dl4j_rope_fwd", "dl4j_rope_bwd"} == set(
        re.findall(r"dl4j_[a-z]+_[a-z_]*?(?=_(?:bh|n)\d)", text))
    for way in ("fwd", "bwd"):
        assert f"dl4j_flash_{way}_bh36_t8192_d128_w512_" in text      # the sliding layers' band
        assert f"dl4j_flash_{way}_bh24_t8192_d128_bq" in text         # the global layers' triangle
        assert f"dl4j_rope_{way}_bh44_t8192_d128_r128" in text
        assert f"dl4j_rope_{way}_bh32_t8192_d128_r64" in text
    assert "ragged-dot" in text
    for part in ("proj", "rope", "gates", "attend", "out"):
        assert re.search(rf"dl4j\.gatedattention/{part}", text), part
    assert re.search(r"routedexperts/shared", text)
    assert re.search(r"(f32|bf16)\[81920,2048\]", text)          # every assignment a row, [gate | up]
    assert re.search(r"(f32|bf16)\[81920,1024\]", text)
    assert not re.search(r"(f32|bf16)\[8192,12544\]", text)      # the head in row blocks
    assert not re.search(r"f32\[1,(24|36),8192,8192\]", text)    # no materialised scores
