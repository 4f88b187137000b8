"""The step's device time by the program's own scopes (PR 35).

The program names its layers and their parts on JAX's name stack while a
step is traced (`deeplearning4j_tpu.telemetry.device_scope`; the grammar is
docs/TELEMETRY.md "Device scopes"). The stack is every operation's HLO
`op_name`, and the profiler's device trace carries it as the stat `tf_op`
("jit(step)/jvp(dl4j.L3.sublayerblock)/dl4j.routedexperts/product/ragged_dot:")
beside `flops`, `bytes_accessed`, `hlo_category`, `source` — in the plane's
`event_metadata[..].stats`, NOT in the event's own stats, and
`jax.profiler.ProfileData` (all `trace_reduce` uses) exposes only the latter.
So this file reads the `.xplane.pb` once more, with a protobuf wire decoder
of its own (nothing but the standard library), far enough to map an
`XLA Ops` event's name to its metadata's stats; the events themselves still
come from `trace_reduce` (an event's name is its metadata's name).

**Attribution.** Every nanosecond of the main program's runs on chip 0 goes
to the INNERMOST `XLA Ops` event open on it (`span_reduce.exclusive`: a
`while` and its body nest as a thread's spans do), and that event's `tf_op`
says (layer, kind, part path, pass). Pass = forward when no `transpose(`
wrapper is on the stack, else the backward region; inside it a
`rematted_computation` component marks what a `jax.checkpoint` runs again.
The ACCOUNT books the part `grad` to the backward region too, whatever its
stack says (`parse` reports the stack as it is): it is what a `custom_vjp`'s
FORWARD rule makes of the gradient ahead of the backward pass
(`losses.sparse_xent_weighted`, PR 45: a row block's dz, dx and share of dW
while the block's logits are there) and carries no `transpose(`.
An operation with no `dl4j.` component is `unscoped`; time inside a run with
no operation open is `no operation`.

**Known limits.** A fusion carries ONE instruction's `op_name`: a fusion XLA
built across two parts (a weight gradient with Adam's update fused in, a
norm fused into the product that follows) is booked whole to one of them.
And a compiler pass that REPLACES an instruction may drop its stack: libtpu
rewrites `lax.ragged_dot` into a custom call `ragged-dot-none.N` whose
`op_name` is "ragged-dot-none" and nothing else (my chip runs, PR 35: 130 ms
of Qwen3-Next's 933 ms step). Such an operation is known by its instruction's
name alone (`ADOPTED`): it is booked to the part that name stands for, in
the layer of the last operation before it that carries a scope of the same
kind, and in the region its OPERANDS say (the event's name is the whole
instruction, operands included, and the operations that made them ran
before it): backward where one of them was made by the backward pass (a
cotangent), else recompute where one was recomputed, else — all of them
parameters, kept values or primal results — the neighbour's region. (The
neighbour alone is not enough: XLA schedules a recomputed gather right in
front of the backward's first grouped product.)

A program without scopes (the parent of PR 35) gives `None` everywhere and
the result line leaves the metrics out. A program WITH the seam whose trace
holds none ran an executable compiled before the scopes existed (JAX's
persistent compile-cache key strips debug info, so a scoped and an unscoped
step share a key): `step_scoped_share.train` then reads 0, one line says so,
and the cache wants clearing.

    python3 -m benchmark.scope_reduce <file.xplane.pb> [chips]   # the tables
"""
from __future__ import annotations

import glob
import os
import re
import struct
import sys
import time
from collections import defaultdict, namedtuple

from benchmark import harness, span_reduce, trace_reduce

PREFIX = "dl4j."
LAYER = re.compile(r"^L([A-Za-z0-9_-]+)\.([a-z0-9_]+)$")
#: name-stack wrappers that hold a path of their own ("transpose(jvp(dl4j.L0.kda))")
TRANSFORM = re.compile(r"^(jvp|transpose|vmap)\((.*)\)$")
RECOMPUTE = "rematted_computation"
CHECKPOINT = "checkpoint"
#: the part a custom_vjp's forward rule makes the gradient's products under
GRADIENT = "grad"
OPERAND = re.compile(r"%([A-Za-z_][\w.\-]*)")
UNSCOPED, NO_OP = "unscoped", "no operation"
#: instruction name's beginning, where the compiler strips its stack -> (kind, parts) it is
#: the work of: XLA's grouped product, called from `ops.linear.grouped_dot`
#: inside `RoutedExperts`' `product` alone
ADOPTED = {"ragged-dot": ("routedexperts", ("product",))}


# ---------------------------------------------------------------------------
# the protobuf wire format, as far as an XSpace needs it
# ---------------------------------------------------------------------------
def _varint(buf, i):
    n = shift = 0
    while True:
        b = buf[i]
        i += 1
        n |= (b & 0x7F) << shift
        if b < 0x80:
            return n, i
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message: a varint as an int,
    a length-delimited field as a slice of `buf` (no copy of a memoryview),
    a fixed field as its 8 or 4 bytes."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")
        yield number, wire, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entry(buf):
    """A map<int64, message> entry -> (key, the message's bytes)."""
    key, value = 0, b""
    for number, _, v in fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _stat(buf, stat_names):
    """An XStat -> (its name, its value): a number, a string, or the string a
    `ref_value` points at in `stat_metadata`."""
    name, value = None, None
    for number, _, v in fields(buf):
        if number == 1:
            name = stat_names.get(v)
        elif number == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif number in (3, 4):
            value = v
        elif number == 5:
            value = _text(v)
        elif number == 6:
            value = bytes(v)
        elif number == 7:
            value = stat_names.get(v)
    return name, value


def plane_metadata(plane):
    """One XPlane's bytes -> {event metadata id: (name, {stat name:
    value})}. `lines` are skipped by their length."""
    events, stats = {}, {}
    for number, _, v in fields(plane):
        if number == 4:
            key, message = _map_entry(v)
            events[key] = message
        elif number == 5:
            key, message = _map_entry(v)
            stats[key] = next((_text(x) for n, _, x in fields(message) if n == 2), "")
    out = {}
    for key, message in events.items():
        ev_name, ev_stats = "", {}
        for number, _, v in fields(message):
            if number == 2:
                ev_name = _text(v)
            elif number == 5:
                s_name, s_value = _stat(v, stats)
                if s_name is not None:
                    ev_stats[s_name] = s_value
        out[key] = (ev_name, ev_stats)
    return out


def op_metadata(path: str, chip: int = 0) -> dict:
    """{event name: [stats, ..]} of chip `chip`'s plane of an `.xplane.pb`:
    the metadata entries that carry `tf_op` (the HLO operations), one dict
    of stats a `program_id` that has an instruction of that name."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    want = f"/device:TPU:{chip}"
    by_name = defaultdict(list)
    for number, _, plane in fields(buf):
        if number != 1:
            continue
        # the name is a small field before the lines: peek, skip other planes
        name = next((_text(v) for n, _, v in fields(plane) if n == 2), "")
        if name != want:
            continue
        for ev_name, stats in plane_metadata(plane).values():
            if "tf_op" in stats and stats not in by_name[ev_name]:
                by_name[ev_name].append(stats)
    return dict(by_name)


# ---------------------------------------------------------------------------
# the grammar
# ---------------------------------------------------------------------------
Scope = namedtuple("Scope", "layer kind parts backward recompute checkpoints")


def _components(stack: str, out: list, transforms: list) -> None:
    """The stack's components in order, a transform's inner path inlined."""
    depth, start = 0, 0
    for i, ch in enumerate(stack + "/"):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            part = stack[start:i]
            start = i + 1
            m = TRANSFORM.match(part)
            if m:
                transforms.append(m.group(1))
                _components(m.group(2), out, transforms)
            elif part:
                out.append(part)


def part_words() -> frozenset:
    """The words the program's seam lets a layer name a part with; empty for
    a program without the seam (it opens no scope either)."""
    try:
        from deeplearning4j_tpu.telemetry import trace
    except ImportError:
        return frozenset()
    return frozenset(getattr(trace, "SCOPE_PARTS", ()))


def parse(tf_op: str, parts=None):
    """A `tf_op` (or an HLO `op_name`) -> `Scope`, or None when the stack
    holds no `dl4j.` component. On the chip the stat ends in ":" and an
    optional op type, which is dropped with the primitive's own name (the
    last component). `layer` is the index (or vertex name) of the layer
    scope, None under `dl4j.loss` / `dl4j.update`; `kind` the INNERMOST
    kind (a block's nested layer wins over the block); `parts` the part
    words met after the first kind, in order, a repeat dropped."""
    if PREFIX not in tf_op:
        return None
    words = part_words() if parts is None else parts
    comps, transforms = [], []
    _components(tf_op.split(":", 1)[0], comps, transforms)
    layer = kind = None
    path = []
    for c in comps[:-1]:
        if c.startswith(PREFIX):
            m = LAYER.match(c[len(PREFIX):])
            if m:
                layer, kind = m.group(1), m.group(2)
            else:
                kind = c[len(PREFIX):]
        elif kind is not None and c in words and path[-1:] != [c]:
            path.append(c)
    if kind is None:
        return None
    return Scope(layer, kind, tuple(path), "transpose" in transforms,
                 RECOMPUTE in comps, comps.count(CHECKPOINT))


# ---------------------------------------------------------------------------
# the account
# ---------------------------------------------------------------------------
class Account:
    """Seconds of the main program's runs on one chip by scope.
    `rows[(layer, kind, parts)]` = [forward, backward region, of it
    recompute, calls] — seconds summed over the runs, `calls` the events
    that opened in them; `steps` the runs; `step_s` their summed time."""

    def __init__(self, steps: int, step_s: float):
        self.steps, self.step_s = steps, step_s
        self.rows = defaultdict(lambda: [0.0, 0.0, 0.0, 0])
        self.categories = defaultdict(float)    # of the unscoped: by hlo_category

    def _row(self, scope):
        return self.rows[(None, UNSCOPED, ()) if scope is None
                         else (scope.layer, scope.kind, scope.parts)]

    def call(self, scope):
        self._row(scope)[3] += 1

    def add(self, scope, seconds, category=""):
        row = self._row(scope)
        if scope is None:
            row[0] += seconds
            self.categories[category] += seconds
        else:
            row[1 if scope.backward else 0] += seconds
            if scope.backward and scope.recompute:
                row[2] += seconds

    def seconds(self, keep) -> float:
        """Seconds (both passes) of the rows `keep(layer, kind, parts)` admits."""
        return sum(r[0] + r[1] for k, r in self.rows.items() if keep(*k))

    @property
    def scoped_s(self) -> float:
        return self.seconds(lambda layer, kind, parts: kind not in (UNSCOPED, NO_OP))

    @property
    def forward_s(self) -> float:
        return sum(r[0] for (_, kind, _), r in self.rows.items()
                   if kind not in (UNSCOPED, NO_OP))

    @property
    def recompute_s(self) -> float:
        """What runs under `rematted_computation`: a checkpoint's forward again."""
        return sum(r[2] for r in self.rows.values())

    def mixers(self) -> set:
        """The kinds that open a `rule`: the recurrent mixers."""
        return {kind for (_, kind, parts) in self.rows if parts[:1] == ("rule",)}

    # ---- the printed tables ------------------------------------------------
    def _table(self, title, key):
        by = defaultdict(lambda: [0.0, 0.0, 0.0, 0])
        for k, r in self.rows.items():
            into = by[key(*k)]
            for i in range(4):
                into[i] += r[i]
        last = {UNSCOPED: 1, NO_OP: 2}
        names = sorted(by, key=lambda n: (last.get(n, 0), -(by[n][0] + by[n][1])))
        ms = 1e3 / max(self.steps, 1)
        lines = [f"[bench] device ms a step by dl4j scope, {title} "
                 f"({self.steps} steps of {self.step_s * ms:.2f} ms)",
                 f"[bench]   {'scope':<44}{'forward':>10}{'backward':>10}"
                 f"{'(recompute)':>12}{'both':>10}{'share':>8}{'calls':>8}"]
        for n in names:
            f, b, r, c = by[n]
            lines.append(
                f"[bench]   {n:<44}{f * ms:>10.2f}{b * ms:>10.2f}{r * ms:>12.2f}"
                f"{(f + b) * ms:>10.2f}{100 * (f + b) / self.step_s:>7.2f}%"
                f"{c / max(self.steps, 1):>8.0f}")
        f = sum(v[0] for n, v in by.items() if n not in last)
        b = sum(v[1] for v in by.values())
        rest = sum(v[0] for n, v in by.items() if n in last)
        lines.append(f"[bench]   forward {100 * f / self.step_s:.2f} % + backward region "
                     f"{100 * b / self.step_s:.2f} % + unscoped and no operation "
                     f"{100 * rest / self.step_s:.2f} % of the step")
        return lines

    def tables(self):
        def by_part(layer, kind, parts):
            return kind if kind in (UNSCOPED, NO_OP) else f"{kind}.{'/'.join(parts) or '-'}"

        def by_layer(layer, kind, parts):
            return kind if layer is None else f"L{layer}"

        lines = self._table("kind.part over the layers of a kind", by_part)
        lines += self._table("layer", by_layer)
        if self.categories:
            cats = sorted(self.categories.items(), key=lambda kv: -kv[1])
            ms = 1e3 / max(self.steps, 1)
            lines.append("[bench]   unscoped by hlo_category, ms a step: "
                         + ", ".join(f"{c or '?'} {s * ms:.2f}" for c, s in cats[:8]))
        return lines


def _clip(events, runs):
    """The parts of `events` [(s, e, name)] inside the sorted disjoint
    `runs` [(s, e)]; an event that straddles a run's edge is cut to it."""
    out, j = [], 0
    for s, e, name in sorted(events):
        while j < len(runs) and runs[j][1] <= s:
            j += 1
        k = j
        while k < len(runs) and runs[k][0] < e:
            out.append((max(s, runs[k][0]), min(e, runs[k][1]), name))
            k += 1
    return out


def _resolve(metas, program_id):
    """The stats of the instruction in the main program, where instructions
    of two programs share a name."""
    for stats in metas:
        if str(stats.get("program_id")) == program_id:
            return stats
    return metas[0]


def _booked(scope):
    """The scope as the account books it: gradient products made in a
    forward visit (part `grad`) are the backward region's."""
    if scope is not None and GRADIENT in scope.parts:
        return scope._replace(backward=True)
    return scope


def _region(scope, name, made):
    """`scope` with the region the operands of instruction `name` say:
    `made` {instruction: Scope} of the operations that ran before it."""
    head = name.split(" = ", 1)
    operands = [made[o] for o in OPERAND.findall(head[1] if len(head) > 1 else "")
                if made.get(o) is not None]
    if any(o.backward and not o.recompute for o in operands):
        return scope._replace(backward=True, recompute=False)
    if any(o.recompute for o in operands):
        return scope._replace(backward=True, recompute=True)
    return scope


def account(ops, module_name, runs, metadata, parts=None):
    """`ops` [(start, end, event name)] of one chip's `XLA Ops` line, the
    main program's name and `runs` [(start, end)], `metadata` from
    `op_metadata` -> `Account`, or None when no operation inside the runs
    has a `tf_op` (a trace without the metadata)."""
    if not runs:
        return None
    words = part_words() if parts is None else parts
    m = re.search(r"\((\d+)\)$", module_name or "")
    program_id = m.group(1) if m else ""
    inside = _clip(ops, runs)
    acct = Account(len(runs), sum(e - s for s, e in runs) / 1e9)
    by_name, found = {}, False
    for name in {n for _, _, n in inside}:
        stats = _resolve(metadata[name], program_id) if name in metadata else None
        found = found or stats is not None
        by_name[name] = ((_booked(parse(stats["tf_op"], words)), stats.get("hlo_category", ""))
                         if stats else (None, "no metadata"))
    if not found:
        return None
    # one scope an EVENT, in the order they open: what the compiler stripped
    # of its stack adopts the layer of the last scoped event of its kind and
    # the region of what made its operands
    scopes, last, made = [], {}, {}
    for _, _, name in inside:
        scope, category = by_name[name]
        short = trace_reduce.short(name)
        if scope is not None:
            last[scope.kind] = scope
        else:
            kind, parts = next((v for k, v in ADOPTED.items() if short.startswith(k)),
                               (None, ()))
            if kind in last:
                scope = _region(last[kind]._replace(parts=parts), name, made)
        made[short] = scope
        scopes.append((scope, category))
        acct.call(scope)
    covered = 0
    for s, e, i in span_reduce.exclusive([(s, e, i) for i, (s, e, _) in enumerate(inside)]):
        scope, category = scopes[i]
        acct.add(scope, (e - s) / 1e9, category)
        covered += e - s
    acct.rows[(None, NO_OP, ())][0] += acct.step_s - covered / 1e9
    return acct


# ---------------------------------------------------------------------------
# a run's capture, read once
# ---------------------------------------------------------------------------
_cache = {}


def program_has_seam() -> bool:
    try:
        from deeplearning4j_tpu import telemetry
    except ImportError:
        return False
    return hasattr(telemetry, "device_scope")


def capture_file(cell_name: str):
    """The cell's device-only capture, or None."""
    files = sorted(glob.glob(os.path.join(
        harness.TRACE_DIR, cell_name, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def scope_account(run):
    """The `Account` of the run's device-only capture (chip 0), read and
    printed once a run; None for a program without the seam, a run without
    a capture, or a trace without `tf_op`."""
    if not program_has_seam():
        return None
    path = capture_file(run.cell["name"])
    if path is None:
        return None
    if path not in _cache:
        t0 = time.perf_counter()
        name, runs = run.trace.main_module()
        acct = account(run.trace.ops[0], name, runs, op_metadata(path))
        took = time.perf_counter() - t0
        print(f"[bench] scope_reduce read {os.path.getsize(path) / 1e6:.1f} MB "
              f"of {path} in {took:.2f} s", flush=True)
        if acct is not None:
            for line in acct.tables():
                print(line, flush=True)
            if not acct.scoped_s:
                print("[bench] no dl4j scope in the device trace although the program "
                      "opens them: the step's executable was compiled before the scopes "
                      "existed (the compile-cache key strips names) — clear the compile "
                      "cache (docs/TELEMETRY.md, Device scopes)", flush=True)
        _cache[path] = acct
    return _cache[path]


def between_projections(kind: str):
    """`keep` for what a mixer of `kind` does between its projections: every
    part under its scope but `proj` and `out` — the convolutions, gates,
    chunk rule, norm and gate, re-tiling, counters, and the partless rows
    (the row loops' own slicing while the core is mapped over rows).
    Whatever holds that work — a `while` over row groups, one call for all
    rows, a jitted function — it reads the same."""
    return lambda layer, k, parts: k == kind and parts[:1] not in (("proj",), ("out",))


def rule_of(kind: str):
    """`keep` for the chunk rule alone of a mixer of `kind`."""
    return lambda layer, k, parts: k == kind and parts[:1] == ("rule",)


def roofline(run, keep, flops: str, bytes_: str):
    """Per cent of its roofline at which the work under `keep` ran: the
    least time the chip could take for the operations and bytes that
    `run.flops.<flops>` / `<bytes_>` count for a step (the larger of ops /
    peak FLOP/s and bytes / peak B/s) over the device seconds under `keep`,
    both passes and what is recomputed — a kernel run twice halves its
    share. None where the configuration's flops file has no such functions,
    there is no account or nothing ran under `keep`."""
    if not hasattr(run.flops, flops):
        return None
    acct = scope_account(run)
    measured = acct.seconds(keep) if acct is not None else 0.0
    if not measured:
        return None
    rows = run.counters["rows_per_step"] // run.cell["chips"]
    least = max(getattr(run.flops, flops)(run.cfg, rows) / run.peaks["bf16_flops_per_s"],
                getattr(run.flops, bytes_)(run.cfg, rows) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * acct.steps / measured


def share(run, keep=None):
    """Per cent of the step's device time under the scopes `keep(layer,
    kind, parts)` admits (default: any scope), both passes; None where
    there is no account or nothing matched."""
    acct = scope_account(run)
    if acct is None or not acct.step_s:
        return None
    s = acct.scoped_s if keep is None else acct.seconds(keep)
    if keep is not None and not s:
        return None
    return 100.0 * s / acct.step_s


def _main(path, chips):
    red = trace_reduce.reduce_file(path, chips)
    name, runs = red.main_module()
    acct = account(red.ops[0], name, runs, op_metadata(path))
    print("\n".join(acct.tables()) if acct else "no tf_op in this trace")


if __name__ == "__main__":
    _main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 1)
