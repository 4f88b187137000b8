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
    # 15 279 289 344 B (PR 51; 10.77 GB before PRs 39-50 kept o, then q k v, then h)
    assert 10e9 < total < 16e9, total
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
    grouped_products_are_booked_by_their_operands(text)


def grouped_products_are_booked_by_their_operands(text):
    """The REAL step's grouped products through `scope_reduce.account`, no
    chip: libtpu strips a `ragged-dot` of its stack, so the account books it
    by what made its operands. An expert layer's backward region holds ONE
    recomputed product (the second: `h` is kept, PR 50) and four of the
    backward pass; XLA schedules the recomputed gather right in front of the
    first product's weight gradient and a backward fusion in front of the
    recomputed product, so the neighbour alone books both wrongly. A weight
    gradient ([experts, ..]) counts 1 us here, the other products 1 ms, every
    other instruction 1 ns."""
    from benchmark import scope_reduce, trace_reduce
    from benchmark.tests.step_hlo import entry_events

    def ns(instruction):
        if not trace_reduce.short(instruction).startswith("ragged-dot-none"):
            return 1
        return 1000 if re.match(r"bf16\[16,", instruction.split(" = ", 1)[1]) else 1000000

    ops, metadata = entry_events(text, ns)
    acct = scope_reduce.account(ops, "jit_step(7)", [(0, ops[-1][1] + 1)], metadata)
    products = {layer: [round(v * 1e9) // 1000 for v in row[:3]]
                for (layer, kind, parts), row in acct.rows.items()
                if kind == "routedexperts" and parts == ("product",)}
    # forward two products; backward region: one again + two of the backward + two weight gradients
    assert products == {layer: [2000, 3002, 1000] for layer in ("4", "6", "8", "10")}, products

