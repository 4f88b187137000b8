"""The program's own spans, read two ways (PR 24).

**The per-fit phase account** (`deeplearning4j_tpu.telemetry.fit_log()`): the
program keeps, gate on or off, what each span name added during each of its
last fits. `fit_entry` picks the fit that WAS the measured window, and the
`*_ms.train` readers divide a phase's seconds by its steps. Undistorted: the
window's capture is device-only.

**The host capture** (`<harness.TRACE_DIR>/<cell>.host/`, the 2 s capture
with the host tracer on): the program's spans are `dl4j.*` events there, on
the device trace's clock. `idle_by_span` gives every idle nanosecond of chip
0 to the INNERMOST `dl4j.*` span open at that instant on the fit thread (the
line that carries `dl4j.step`) — exclusive, unlike `breakdown.idle_gaps`.
That capture slows the feed, so it is read for shares of the idle time, not
for durations.

A program without these spans (the parent of PR 24) gives `None` everywhere,
and the result line leaves the metric out.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

from benchmark import harness, trace_reduce

PREFIX = "dl4j."
STEP = "dl4j.step"
LEAF_PHASES = ("etl", "put", "dispatch", "score_wait")   # + listeners


# ---------------------------------------------------------------------------
# the phase account
# ---------------------------------------------------------------------------
def fit_entry(run):
    """The `fit_log()` entry of the measured window: as many steps as the
    driver counted, and a wall time just inside the window's (the window
    closes on `block_until_ready` after `fit` returns). None when the
    program keeps no such log or no fit matches — never the nearest guess."""
    from deeplearning4j_tpu import telemetry

    log = getattr(telemetry, "fit_log", None)
    steps, window_s = run.counters.get("steps"), run.counters.get("window_s")
    if log is None or not steps or not window_s:
        return None
    fits = [f for f in log() if f["steps"] == steps
            and 0.9 * window_s <= f["wall_s"] <= window_s]
    if not fits:
        return None
    return min(fits, key=lambda f: window_s - f["wall_s"])


def phase(run, name):
    """(the window's fit, its account of span `name`) or (None, None)."""
    fit = fit_entry(run)
    p = fit["phases"].get(name) if fit else None
    return (fit, p) if p else (None, None)


def phase_ms(run, name):
    """Milliseconds a step of the window spent inside span `name`."""
    fit, p = phase(run, name)
    return None if p is None else 1e3 * p["total_s"] / fit["steps"]


def unspanned_ms(run):
    """The fit loop's self time per step: the fit's wall time less its leaf
    phases. None unless all four of etl/put/dispatch/score_wait are there
    (`listeners` counts 0 when the fit had none to call)."""
    fit = fit_entry(run)
    if fit is None or any(n not in fit["phases"] for n in LEAF_PHASES):
        return None
    spanned = sum(fit["phases"][n]["total_s"] for n in LEAF_PHASES)
    spanned += fit["phases"].get("listeners", {"total_s": 0.0})["total_s"]
    return 1e3 * (fit["wall_s"] - spanned) / fit["steps"]


# ---------------------------------------------------------------------------
# the host capture
# ---------------------------------------------------------------------------
def exclusive(events):
    """[(start, end, name)] of ONE thread, nested as spans of a thread are
    -> disjoint [(start, end, name)], each instant given to the innermost
    span open on it."""
    out, stack, cur = [], [], None   # stack of (end, name)

    def close_until(t):
        nonlocal cur
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if cur < end:
                out.append((cur, end, name))
                cur = end

    for s, e, name in sorted(events, key=lambda t: (t[0], -t[1])):
        close_until(s)
        if stack:
            if cur < s:
                out.append((cur, s, stack[-1][1]))
            e = min(e, stack[-1][0])    # a child cannot outlast its parent
        stack.append((e, name))
        cur = s
    close_until(float("inf"))
    return out


def fit_thread_spans(planes):
    """The `dl4j.*` events of the host line that carries most `dl4j.step`
    events: [(start, end, name)], or [] when no line has one."""
    best, best_steps = [], 0
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                    ev.name.split("#", 1)[0])
                   for ev in line.events if ev.name.startswith(PREFIX)]
            steps = sum(1 for _, _, n in evs if n == STEP)
            if steps > best_steps:
                best, best_steps = evs, steps
    return best


def attribute(gaps, spans):
    """Seconds of the merged idle `gaps` under each span name, exclusively,
    plus "unattributed" (under no span) and "idle" (all of it)."""
    by = defaultdict(list)
    for s, e, name in exclusive(spans):
        by[name].append((s, e))
    out, covered = {}, []
    for name, ivs in by.items():
        u = trace_reduce.union(ivs)
        covered += u
        out[name] = trace_reduce.total(
            trace_reduce.subtract(gaps, trace_reduce.subtract(gaps, u))) / 1e9
    out["unattributed"] = trace_reduce.total(
        trace_reduce.subtract(gaps, trace_reduce.union(covered))) / 1e9
    out["idle"] = trace_reduce.total(gaps) / 1e9
    return out


def attribute_planes(planes, chips: int):
    """`attribute` for chip 0 of a capture's planes; None when the fit
    thread left no `dl4j.step` in it."""
    planes = list(planes)
    spans = fit_thread_spans(planes)
    if not spans:
        return None
    device = [p for p in planes if trace_reduce.DEVICE_PLANE.match(p.name)]
    return attribute(trace_reduce.reduce_planes(device, chips).idle_gaps(0),
                     spans)


_cache = {}


def idle_by_span(run):
    """`attribute_planes` of the cell's host capture, read once a run; None
    when there is no capture or it holds no span of the program."""
    files = sorted(glob.glob(os.path.join(
        harness.TRACE_DIR, run.cell["name"] + ".host", "plugins", "profile",
        "*", "*.xplane.pb")))
    if not files:
        return None
    if files[-1] not in _cache:
        from jax.profiler import ProfileData

        by = attribute_planes(ProfileData.from_file(files[-1]).planes,
                              run.cell["chips"])
        print(f"[bench] chip-0 idle seconds by innermost dl4j span {by}",
              flush=True)
        _cache[files[-1]] = by
    return _cache[files[-1]]


def idle_share(run, name=None):
    """Per cent of chip 0's idle time under span `name`; with no name,
    under any span of the fit thread."""
    by = idle_by_span(run)
    if not by or not by["idle"]:
        return None
    part = by["idle"] - by["unattributed"] if name is None else by.get(name, 0.0)
    return 100.0 * part / by["idle"]
