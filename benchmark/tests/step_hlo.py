#!/usr/bin/env python3
"""Is a change to the program free on the device? Compile every
configuration's train step at its real size for a described v5e (no chip:
as test_compile_v5e*.py do) and write its optimised HLO in a form that two
checkouts can be compared in, byte for byte:

    (cd <checkout A> && python3 benchmark/tests/step_hlo.py dump /tmp/a [config ..])
    (cd <checkout B> && python3 benchmark/tests/step_hlo.py dump /tmp/b [config ..])
    python3 benchmark/tests/step_hlo.py compare /tmp/a /tmp/b

`dump` takes out what carries NAMES and nothing else: `metadata={..}`, the
stack-frame tables in the module's head, the instructions' own names
(renumbered in order of appearance: a Pallas custom call is named after the
last component of its name stack) and, from each Mosaic kernel's payload,
the debug locations (a payload carries the file and line of every Python
frame above its `pallas_call`). `memory_analysis()` goes in a line of its
own. One process a checkout: each imports its own `deeplearning4j_tpu`.
PR 35 (device scopes): all four steps equal the parent's. All eight
configurations since PR 51 (11 min for the first four, ~7 for the rest).
"""
import base64
import os
import re
import sys

CELLS = [("gpt2-small", "train_ids_t1024_b8"), ("qwen3-next-80b-a3b-l4", "train_ids_t8192_b2"),
         ("nemotron-3-nano-30b-a3b-l9", "train_ids_t8192_b2"),
         ("kimi-linear-48b-a3b-l5", "train_ids_t8192_b2"),
         ("kanana-2-30b-a3b-l5", "train_ids_t8192_b2"), ("lfm2-24b-a2b-l5", "train_ids_t8192_b2"),
         ("ouro-2.6b-l6", "train_ids_t8192_b1"), ("laguna-s-2.1-l5", "train_ids_t8192_b1")]
TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def payload(match):
    """A kernel's serialised module, printed without debug locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(match.group(1)))
        return '"body": ' + repr(module.operation.get_asm(enable_debug_info=False))


def canonical(text: str) -> str:
    lines, skipping = [], False
    for line in text.split("\n"):
        if line in TABLES:
            skipping = True
        elif skipping:
            skipping = line != ""
        else:
            lines.append(line)
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", "\n".join(lines))
    text = re.sub(r'"body": ?"([A-Za-z0-9+/=]+)"', payload, text)
    names = {}
    return re.sub(r"%[A-Za-z_][\w.\-]*",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"), text)


def entry_events(text: str, ns=lambda instruction: 1):
    """A compiled step's ENTRY computation as a device trace would show it,
    without a chip: the scheduled instructions in order, back to back, `ns`
    nanoseconds each -> (`ops` [(start, end, instruction)], `metadata`
    {instruction: [stats]}) for `scope_reduce.account`. The instruction is
    the whole text less its `metadata={..}`, as a trace event's name is."""
    entry = text[text.index("\nENTRY "):]
    ops, metadata, t = [], {}, 0
    for line in entry[:entry.index("\n}\n")].split("\n")[1:]:
        line = line.strip().removeprefix("ROOT ").strip()
        if not line.startswith("%"):
            continue
        name = re.sub(r",? ?metadata=\{[^}]*\}", "", line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        if op_name:
            metadata[name] = [{"tf_op": op_name.group(1) + ":", "program_id": "7"}]
        ops.append((t, t + ns(name), name))
        t += ns(name)
    return ops, metadata


def dump(out_dir: str, only=()) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.getcwd())
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies

    from benchmark.tests.test_compile_v5e import load
    from benchmark.tests.test_compile_v5e_ids import compile_ids_step

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    os.makedirs(out_dir, exist_ok=True)
    for conf, traffic in CELLS:
        if only and conf not in only:
            continue
        compiled = compile_ids_step(topo, load("configs", conf), load("traffic", traffic))
        m = compiled.memory_analysis()
        head = (f"memory: arguments {m.argument_size_in_bytes} outputs {m.output_size_in_bytes} "
                f"temporaries {m.temp_size_in_bytes}\n")
        with open(os.path.join(out_dir, conf + ".hlo.txt"), "w") as f:
            f.write(head + canonical(compiled.as_text()))
        print("wrote", conf, head.strip(), flush=True)


def compare(a: str, b: str) -> int:
    worst = 0
    for conf, _ in CELLS:
        if not all(os.path.exists(os.path.join(d, conf + ".hlo.txt")) for d in (a, b)):
            print(f"{conf}: not dumped on both sides")
            continue
        with open(os.path.join(a, conf + ".hlo.txt")) as fa, \
                open(os.path.join(b, conf + ".hlo.txt")) as fb:
            ta, tb = fa.read(), fb.read()
        same = ta == tb
        print(f"{conf}: {'byte-equal' if same else 'DIFFER'} ({len(ta)} / {len(tb)} bytes)")
        if not same:
            worst = 1
            for i, (x, y) in enumerate(zip(ta.split("\n"), tb.split("\n"))):
                if x != y:
                    print(f"  first at line {i}:\n  < {x[:300]}\n  > {y[:300]}")
                    break
    return worst


if __name__ == "__main__":
    if sys.argv[1:2] == ["dump"] and len(sys.argv) >= 3:
        dump(sys.argv[2], sys.argv[3:])
    elif sys.argv[1:2] == ["compare"] and len(sys.argv) == 4:
        sys.exit(compare(sys.argv[2], sys.argv[3]))
    else:
        sys.exit(__doc__)
