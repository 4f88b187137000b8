"""From a profiler trace (`.xplane.pb`) to the numbers the per-layer metrics
read. Needs nothing but JAX (`jax.profiler.ProfileData`).

What a TPU trace holds (looked at by hand before this was written, PR 23):
one plane per chip, "/device:TPU:<n>", with a line "XLA Modules" (one event
per run of a compiled program, "jit_step(<hash>)") and a line "XLA Ops" (one
event per HLO operation that ran; the event's name is the whole instruction,
"%fusion.1380 = (bf16[50257]{...}, ...) fusion(...), kind=kLoop, ...", and a
Pallas kernel's instruction carries its `kernel_name`:
"%transpose_jvp_dl4j_flash_bwd_dkv_bh96_t1024_..."). Host threads are lines
of the plane "/host:CPU": the runtime's own spans ("XlaLinearize" is the
host re-tiling an array for the device, "np.asarray(jax.Array)" the host
waiting for a result), the benchmark's `TraceAnnotation`s ("bench.*") and
the program's own spans ("dl4j.score_wait", "dl4j.dispatch", ...: PR 24),
on the same clock.

    python3 -m benchmark.trace_reduce <file.xplane.pb> [chips]   # a summary
"""
from __future__ import annotations

import re
import statistics
import sys
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
HOST_NAMES = re.compile(
    r"^(bench\.|dl4j\.|XlaLinearize$|np\.asarray\(jax\.Array\)|PjitFunction\(|"
    r"DevicePut|shard_args$|tpu::System::TransferToDevice$)")
KERNEL = re.compile(r"dl4j_[a-z]+(?:_[a-z]+)*?(?=_(?:bh|n|b)\d)")
SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
BREAKDOWN_ROWS = 10     # entries a list of the result's `breakdown` may hold (the contract)


def union(intervals):
    """Sorted, merged [(start, end)] of possibly nested/overlapping ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of the merged intervals `a` not covered by the merged `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def short(name: str) -> str:
    """The instruction's own name: "%fusion.1380 = ..." -> "fusion.1380"."""
    return name.split(" = ", 1)[0].lstrip("%")


def family(name: str) -> str:
    """A stable name for an operation: a Pallas kernel is its family
    (dl4j_flash_fwd, dl4j_flash_bwd, dl4j_gdn_fwd, ...) whatever the layer,
    the shapes in its name and the autodiff prefix; everything else loses
    its numeric suffix (fusion.123 -> fusion)."""
    s = short(name)
    m = KERNEL.search(s)
    if m:
        return m.group(0)
    return re.sub(r"[.\d]+$", "", s) or s


def describe(name: str) -> str:
    """A label for the breakdown: a kernel's family, or the instruction's
    name with the shapes it produces ("fusion.1380 -> bf16[8,1024,50257]")."""
    m = KERNEL.search(short(name))
    if m:
        return m.group(0)
    head = name.split(" = ", 1)
    if len(head) < 2:
        return short(name)[:100]
    result = re.split(r"\) [a-z-]+\(|\} [a-z-]+\(", head[1], maxsplit=1)[0]
    shapes = SHAPE.findall(result)
    biggest = max(shapes, key=len) if shapes else ""
    return f"{short(name)} -> {biggest}"[:100]


class Reduction:
    """Per chip: merged busy intervals, per-op-family seconds, module runs,
    collective intervals; and the host's annotated spans."""

    def __init__(self, chips: int):
        self.chips = chips
        self.busy = {}            # chip -> merged [(s, e)] ns
        self.ops = {}             # chip -> [(s, e, name)]
        self.modules = {}         # chip -> [(s, e, name)]
        self.host = []            # [(s, e, name)]
        self.t_min = None
        self.t_max = None

    # ---- device time -----------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.t_max - self.t_min) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(total(b) for b in self.busy.values()) / self.chips / 1e9

    def family_seconds(self, chip=0) -> dict:
        """Seconds by operation family on one chip. Events of a family may
        nest (a while loop and its body), so each family is a union."""
        by = defaultdict(list)
        for s, e, name in self.ops[chip]:
            by[family(name)].append((s, e))
        return {k: total(union(v)) / 1e9 for k, v in by.items()}

    def kernel_seconds(self, family_prefix: str, chip=0) -> float:
        """Device seconds of the kernels whose family starts with the
        prefix ("dl4j_flash" = forward, dq and dkv)."""
        return total(union([(s, e) for s, e, n in self.ops[chip]
                            if family(n).startswith(family_prefix)])) / 1e9

    # ---- programs ----------------------------------------------------------
    def main_module(self, chip=0):
        """The program that took most device time (the train step, or the
        server's forward), as (name, [(s, e)] sorted)."""
        by = defaultdict(list)
        for s, e, name in self.modules[chip]:
            by[re.sub(r"\(\d+\)$", "", name)].append((s, e))
        if not by:
            return None, []
        name = max(by, key=lambda k: sum(e - s for s, e in by[k]))
        return name, sorted(by[name])

    def module_gaps_ms(self, chip=0):
        """Gaps on the device timeline between the end of one run of the
        main program and the start of the next."""
        _, runs = self.main_module(chip)
        return [(runs[i + 1][0] - runs[i][1]) / 1e6
                for i in range(len(runs) - 1)]

    # ---- collectives -------------------------------------------------------
    def collective_exposed_s(self, chip=0) -> float:
        """Collective time on one chip during which no other operation runs
        there."""
        coll = union([(s, e) for s, e, n in self.ops[chip]
                      if COLLECTIVE.match(short(n))])
        rest = union([(s, e) for s, e, n in self.ops[chip]
                      if not COLLECTIVE.match(short(n))])
        return total(subtract(coll, rest)) / 1e9

    # ---- the breakdown -----------------------------------------------------
    def idle_gaps(self, chip=0):
        """Merged [(start, end)] in which nothing ran on the chip, from the
        first device operation of the trace to the last."""
        return subtract([(self.t_min, self.t_max)], self.busy[chip])

    def idle_by_host_span(self, chip=0) -> dict:
        """Seconds of the chip's idle time that each kind of host span
        overlaps. Spans nest and run on several threads, so the shares are
        not exclusive: each says how much of the idle time that activity
        was going on in — the program's `dl4j.step` holds its
        `dl4j.score_wait`, and both count (`span_reduce.idle_by_span` is the
        exclusive attribution). "no host span" is idle time none overlaps."""
        gaps = self.idle_gaps(chip)
        by = defaultdict(list)
        for s, e, name in self.host:
            by[re.sub(r"\(.*", "", name) if name.startswith("PjitFunction")
               else name].append((s, e))
        out, covered = {}, []
        for name, ivs in by.items():
            u = union(ivs)
            covered += u
            out[name] = total(subtract(gaps, subtract(gaps, u))) / 1e9
        out["no host span"] = total(subtract(gaps, union(covered))) / 1e9
        return out

    def device_ops(self, chip=0):
        """[[label, seconds], ...], `BREAKDOWN_ROWS` entries by seconds: every `dl4j_*` kernel family that ran, WHATEVER ITS RANK —
        a family under 1 % of a step never reached the ten largest, and a
        claim on it had no row (PERF.md section 7, PR 41 (c)) — and beside
        them the largest other operations, by instruction."""
        by = defaultdict(list)
        for s, e, name in self.ops[chip]:
            by[describe(name)].append((s, e))
        ranked = sorted(((total(union(v)) / 1e9, k) for k, v in by.items()), reverse=True)
        kernels = [r for r in ranked if r[1].startswith("dl4j_")][:BREAKDOWN_ROWS]
        others = [r for r in ranked
                  if not r[1].startswith("dl4j_")][:BREAKDOWN_ROWS - len(kernels)]
        return [[k, v] for v, k in sorted(kernels + others, reverse=True)]

    def idle_gaps_by_host(self, chip=0):
        """The ten kinds of host span that overlap most of the chip's idle
        time: [[span, seconds of idle time it overlaps], ...]."""
        idle = sorted(((v, k) for k, v in self.idle_by_host_span(chip).items()
                       if v > 0), reverse=True)[:BREAKDOWN_ROWS]
        return [[k, v] for v, k in idle]

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops(),
                "idle_gaps": self.idle_gaps_by_host()}


def reduce_file(path: str, chips: int) -> Reduction:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, chips)


def reduce_planes(planes, chips: int) -> Reduction:
    """`planes`: objects with `.name` and `.lines`; a line has `.name` and
    `.events`; an event has `.name`, `.start_ns`, `.duration_ns`."""
    red = Reduction(chips)
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                evs = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                        ev.name) for ev in line.events]
                if line.name == OPS_LINE:
                    red.ops[chip] = evs
                    red.busy[chip] = union([(s, e) for s, e, _ in evs])
                else:
                    red.modules[chip] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if HOST_NAMES.match(ev.name):
                        red.host.append((int(ev.start_ns),
                                         int(ev.start_ns + ev.duration_ns),
                                         ev.name))
    if len(red.busy) != chips or not all(red.busy.values()):
        raise RuntimeError(
            f"trace holds device operations for chips {sorted(red.busy)}, "
            f"the cell runs on {chips}")
    for chip in red.busy:
        red.modules.setdefault(chip, [])
    red.t_min = min(b[0][0] for b in red.busy.values())
    red.t_max = max(b[-1][1] for b in red.busy.values())
    return red


def _dump(path, chips):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs), "events;",
                  [e.name for e in evs[:4]])
    red = reduce_planes(data.planes, chips)
    print("window_s", red.window_s, "busy_s", red.busy_s)
    print("main module", red.main_module()[0], len(red.main_module()[1]), "runs")
    gaps = red.module_gaps_ms()
    if gaps:
        print("module gap ms median", statistics.median(gaps))
    print(red.breakdown())


if __name__ == "__main__":
    _dump(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 1)
