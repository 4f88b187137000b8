"""The `ouro_train_t8192_b1` train step compiled at its real size for a
described v5e (as test_compile_v5e_lfm2.py does for its cell): 510 M
parameters at 16 B are 8.15 GB, and the loop keeps four passes' block inputs
and flash outputs beside a gradient tree that is whole only when the backward
has reached pass 1, so the step must fit one chip (25 % .. 100 % of 16 GB by
`memory_analysis()`, recorded in PERF.md) with remat per nested block per
pass and the four heads + loss in row blocks; it must admit the flash kernels
at 16 heads of 128, t 8192, at one forward call site a layer application (24:
the passes are unrolled, and a block's recompute keeps the kernel's output),
open the looped stack's scope around the nested blocks' own, the `exit` part
under `dl4j.loss`, and materialise neither a [.., 49152] logits array of all
rows nor the scores.

One file, topology inside a module fixture: only the worker that is given
this file loads the TPU library."""
import re

from benchmark.tests.test_compile_v5e import load, step_bytes, topo  # noqa: F401
from benchmark.tests.test_compile_v5e_ids import compile_ids_step


def test_ouro_step_fits_one_chip(topo):  # noqa: F811
    cfg = load("configs", "ouro-2.6b-l6")
    compiled = compile_ids_step(topo, cfg, load("traffic", "train_ids_t8192_b1"))
    total = step_bytes(compiled)
    m = compiled.memory_analysis()
    print(f"ouro step: {total} bytes; arguments {m.argument_size_in_bytes} "
          f"outputs {m.output_size_in_bytes} aliased {m.alias_size_in_bytes} "
          f"temporaries {m.temp_size_in_bytes}")
    assert 0.25 * 16e9 < total < 15.75e9, total          # 14 796 995 072 B (PR 51)
    assert 6.1e9 < m.argument_size_in_bytes < 6.2e9       # ONE pass's weights and Adam's two moments
    text = compiled.as_text()
    # flash and, since PR 46, the half-split rotation with the split into heads
    assert {"dl4j_flash_fwd", "dl4j_flash_bwd", "dl4j_rope_fwd", "dl4j_rope_bwd"} == set(
        re.findall(r"dl4j_[a-z]+_[a-z_]*?(?=_(?:bh|n)\d)", text))
    assert "dl4j_flash_fwd_bh16_t8192_d128" in text and "dl4j_flash_bwd_bh16_t8192_d128" in text
    # one forward call a layer application: REMAT_KEEP keeps each pass's flash output
    applications = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    assert len(re.findall(r"custom-call\(.*dl4j_flash_fwd", text)) == applications
    assert len(re.findall(r"custom-call\(.*dl4j_flash_bwd", text)) == applications
    assert re.search(r"dl4j\.L1\.loopedstack.*dl4j\.L0\.sublayerblock.*dl4j\.gatedattention/attend",
                     text)
    assert re.search(r"dl4j\.L1\.loopedstack.*dl4j\.L12\.rmsnorm", text)
    assert re.search(r"dl4j\.loss\)?/exit/", text)
    assert not re.search(r"(f32|bf16)\[(1,4,8192|32768),49152\]", text)   # the heads in row blocks
    assert not re.search(r"f32\[1,16,8192,8192\]", text)                  # no materialised scores
