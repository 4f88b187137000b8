"""jaxlint — AST purity linter for this repo's own JAX sources.

The defect classes the round-5 advisor found by hand (ADVICE.md) are all
*statically detectable*: inconsistent env-gate parsing, wrong-dtype
custom_vjp cotangents, import-time array work, impure RNG and Python
branching inside traced code. This module catches them repo-wide at lint
time — the "catch it at graph-construction time" philosophy applied to
the framework's own sources.

Rule catalogue (stable IDs; docs/ANALYZER.md):

    JX001  raw `os.environ` read of a DL4J_TPU_* gate outside
           util/envflags.py (gates must share ONE normalized parse)
    JX002  `jnp.zeros_like(...)` inside a defvjp-registered backward
           function — integer primals need a float0 cotangent; use
           util.cotangent.zeros_cotangent
    JX003  jnp/lax/jax.random/jax.nn compute (or backend queries) executed
           at module import time — imports must stay array-free so
           importing the package never initializes a backend
    JX004  Python-level RNG (`random.*`, `np.random.*`) inside function
           bodies of traced-code dirs (ops/, nn/layers/) — invisible to
           jit, silently frozen into the trace
    JX005  Python `if`/`while` branching on a jnp/lax call result in
           traced-code dirs — raises TracerBoolConversionError under jit;
           use lax.cond/jnp.where (static queries jnp.ndim/shape/... are
           fine)
    JX006  raw binary write (`open(..., "wb")`, `np.save*`,
           `zipfile.ZipFile(..., "w")`) to a model/checkpoint-looking
           path outside the atomic writer — a crash mid-write tears the
           artifact; route through resilience.checkpoint
           (atomic_write_model / CheckpointManager)
    JX007  `time.time()` subtraction used as a duration — wall clock
           steps under NTP, corrupting timelines/ETAs/rates; use
           time.perf_counter()/time.monotonic() for durations and keep
           time.time() for pure timestamps (which are never subtracted,
           so they never trip this rule — the observability analogue of
           JX006). Tracks names/attributes assigned from time.time()
           file-wide, so `self.start = time.time()` ... `x - self.start`
           is caught across methods.
    JX008  retrace hazard: a `jax.jit`/`jax.pmap` wrapper created inside
           a For/While loop (every iteration builds a fresh wrapper with
           an EMPTY trace cache — each one recompiles), or the
           immediate-invocation form `jax.jit(f)(x)` (the wrapper and
           its cache are discarded after one call, so the enclosing
           function recompiles on every call). The static twin of the
           compile watcher's dynamic retrace detector
           (telemetry/introspect.py): hoist the jit out of the loop /
           bind the jitted function once.
    JX010  per-step host sync in a hot loop: `float(x)` /
           `np.asarray(x)` / `jax.device_get(x)` (bare-name argument),
           `.item()`, or `.block_until_ready()` inside a For/While body
           in the hot-loop dirs (models/, parallel/, training/,
           distributed/ — the distributed masters' split/executor loops
           included) — each one stalls the dispatch pipeline on a
           device->host round-trip every iteration, the exact tax the
           window engine (training/engine.py) amortizes to once per
           window. The static twin of that engine's once-per-window
           rule; the legitimate boundary sites (tbptt chunk loops
           threading host carries, the engine's own once-per-window
           fetch) carry a `# jaxlint: disable=JX010` pragma stating
           why. Heuristic by design: bare-name
           float()/np.asarray()/device_get() arguments are the per-step
           score/metric fetch shape; composite expressions (host
           arithmetic) pass — the dynamic profiler owns those.
    JX015  inner step loop outside the engine: a For/While body in
           models/, parallel/, or distributed/ that executes a train
           step per iteration — calling `_dispatch_step` /
           `_dispatch_std` / `_fit_std_batch` / `_fit_batch_solver` /
           `_fit_tbptt`, or firing
           `listener.iteration_done` by hand — reimplements the inner
           fit loop `training/engine.py` owns. Every such private loop
           silently opts out of the engine's attachments (window gate,
           etl/step spans, watchdog beats, sentry window hooks): route
           the loop through `WindowedFitLoop` (`model._engine_loop()` /
           `engine.run_partition`). The engine itself and the models'
           own step implementations (the tbptt CHUNK loops inside
           `_fit_tbptt`, which are sub-step) are out of scope: the rule
           fires only on loops that drive whole steps from outside
           training/engine.py. A reasoned private loop carries a
           `# jaxlint: disable=JX015` pragma stating why.
    JX011  unbounded blocking wait in cluster-facing code: a zero-argument
           `thread.join()` or `queue.get()` (no timeout) in distributed/,
           parallel/, resilience/, or serving/ — an evicted or
           silently-dead worker must never hang the coordinator, which is
           exactly what an infinite join/get on its thread/queue does
           (the static twin of the membership layer's missed-heartbeat
           detector, distributed/membership.py). Join in bounded slices
           (`t.join(0.02)` in a loop) or pass a timeout; genuinely
           reasoned infinite waits (a consumer idling for its sentinel
           inside a close-protocol-bounded topic) carry a
           `# jaxlint: disable=JX011` pragma stating why.
    JX012  unbounded Event/Condition wait in serving-facing code: a
           zero-argument `.wait()` (`threading.Event.wait()`,
           `Condition.wait()`) in parallel/, serving/, or distributed/ —
           the setter on the other side can be a crashed dispatcher or an
           evicted worker, and an un-timed wait converts that death into
           a caller hung forever. The static twin of the serving drain
           contract ("no caller ever blocks forever",
           serving/runtime.py): every pending-request wait runs in
           bounded slices keyed to its deadline, re-checking dispatcher
           liveness each slice. Pass a timeout (`ev.wait(0.05)` in a
           loop); module-level function calls that merely SPELL `.wait`
           (e.g. `os.wait()`) are out of scope, and a genuinely reasoned
           infinite wait carries a `# jaxlint: disable=JX012` pragma
           stating why.
    JX013  manually-opened trace span: a `.span(...)` / `.start_span(...)`
           call whose result is NOT immediately managed (`with tr.span(...)`,
           `stack.enter_context(tr.span(...))`, or `return`ed for the
           caller to manage). The span context manager attaches a
           TraceContext in __enter__ and MUST detach it in __exit__
           (telemetry/context.py's handoff contract); a span held in a
           variable and entered by hand can miss its finish on an
           exception path, leaking the attached context onto the thread
           so every later span in that thread parents under a dead
           request. Use the context-manager/decorator forms; a reasoned
           manual site carries a `# jaxlint: disable=JX013` pragma.
    JX014  hand-rolled retry sleep: a `time.sleep(...)` inside a
           For/While loop that also contains an `except` handler (the
           catch-sleep-retry shape) in serving/, resilience/, or
           distributed/ — a raw sleep retries in lockstep, so a fleet
           of callers that failed together re-stampedes together (the
           thundering herd `resilience/retry.py`'s DECORRELATED jitter
           exists to prevent, and the hint-honoring client loop
           `serving.submit_with_retry` already implements). A loop that
           derives its delay through `decorrelated_backoff` /
           `retry_call` / `submit_with_retry` is the blessed shape and
           passes; `resilience/retry.py` itself (the implementation) is
           exempt; a reasoned fixed-cadence wait (a poll loop whose
           `except` is incidental) carries a
           `# jaxlint: disable=JX014` pragma stating why.
    JX016  hand-rolled coordinator-role check: a literal comparison of
           `jax.process_index()` against an int constant
           (`jax.process_index() == 0`, `0 != jax.process_index()`)
           outside distributed/runtime.py — the coordinator role is a
           RUNTIME property (`runtime_info().is_coordinator`), not a
           magic number: scattering literal rank tests forks the
           definition the multihost membership/chaos layers key on
           (distributed/multihost.py), and a future coordinator
           election would have to chase every copy. Comparisons against
           non-literals (another rank variable) pass; runtime.py itself
           (the definition site) is exempt; a reasoned literal check
           carries a `# jaxlint: disable=JX016` pragma stating why.
    JX017  anonymous/non-daemon thread in the runtime packages: a
           `threading.Thread(...)` in serving/, distributed/,
           telemetry/, resilience/, or parallel/ without a `name=`
           (every lane in a stall report, trace timeline, or
           lock-inversion bundle is identified by thread name —
           "Thread-12" is undebuggable) or without `daemon=True` (a
           forgotten non-daemon thread wedges interpreter shutdown:
           the process survives its own main()). Threads whose
           lifecycle IS managed (joined before exit, or deliberately
           non-daemon) carry a `# jaxlint: disable=JX017` pragma
           stating why; a non-constant `daemon=` value passes.
    JX018  raw sharding construction outside the layout module: a
           `jax.sharding.PartitionSpec(...)` / `NamedSharding(...)`
           call in models/, parallel/, training/, or distributed/
           anywhere but parallel/mesh.py and parallel/layout.py. The
           FSDP refactor concentrated placement policy in those two
           files (mesh axes + the per-tensor SpecLayout rules); a spec
           constructed elsewhere is a placement decision the layout
           module can't see, audit, or keep consistent with the fsdp
           gather/scatter seams. Sites that genuinely need a local
           spec (device-put plumbing, test-only fixtures living in the
           runtime tree) carry a `# jaxlint: disable=JX018` pragma
           stating why.
    JX019  raw collective call outside the parallel package: a
           `jax.lax.psum / pmean / all_gather / all_to_all / ppermute /
           psum_scatter` call in models/, training/, or distributed/.
           Collectives ARE the communication plan shardlint
           (analysis/sharding.py) statically audits from the layout's
           specs; a hand-placed collective in model or training code is
           traffic the plan can't see, won't cost, and the compiled-HLO
           census will flag as unexplained. Route communication through
           parallel/ (the mesh/layout/wrapper seams) — a site that
           genuinely needs a local collective carries a
           `# jaxlint: disable=JX019` pragma stating why.
    JX020  unbounded buffer in the runtime packages: a
           `queue.Queue()` / `LifoQueue()` / `PriorityQueue()` without
           `maxsize=`, or a `collections.deque(...)` without `maxlen=`
           (and no bounding second positional), in serving/,
           distributed/, or telemetry/. Every queue in the request and
           telemetry paths is a load-shedding decision: an unbounded one
           converts overload into unbounded memory growth and
           unbounded tail latency instead of a typed ShedError — the
           failure mode the admission-control refactor exists to
           prevent. A buffer whose bound lives elsewhere (admission
           enforces the limit before append; the fill is bounded by
           construction) carries a `# jaxlint: disable=JX020` pragma
           stating why.
    JX021  laundered env-gate read: a DL4J_TPU_* gate reaching
           `os.environ` through a variable (`GATE = "DL4J_TPU_X"` ...
           `os.getenv(GATE)`), a membership test
           (`"DL4J_TPU_X" in os.environ`), or a read-modify form
           (`os.environ.pop/.setdefault`) outside util/envflags.py.
           JX001's literal-only match made indirection a loophole: the
           gate still bypasses the one normalized truthy/falsy parse
           (and now also the tuner's live-override overlay, which only
           envflags consults — a laundered read silently ignores
           tuner decisions). Tracks names/attributes assigned a
           DL4J_TPU_* string literal file-wide, JX007-style. Route the
           read through util.envflags, or pragma a reasoned raw site
           with `# jaxlint: disable=JX021`.
    JX022  private telemetry instance: a direct `MetricsRegistry()` or
           `Tracer()` construction outside telemetry/. The fleet
           federation layer (telemetry/aggregate.py) ships ONE frame
           per source built from the process-global registry and trace
           ring; counters incremented into a privately-constructed
           registry and spans recorded into a private ring never reach
           a frame, so they silently vanish from /fleet/metrics, the
           merged Chrome trace, and the federated SLO — observability
           that looks wired up but isn't. Use
           `telemetry.metrics.registry()` / `counter()/gauge()/
           histogram()` and `telemetry.trace.tracer()`; offline tools
           that deliberately build a throwaway instance (a CLI
           converting a stats file, a bundle viewer reconstructing a
           ring) carry a `# jaxlint: disable=JX022` pragma stating why.
    JX009  silent swallow: an `except` handler whose whole body is
           `pass` — the exception AND its traceback vanish, which is
           exactly the failure mode the flight recorder
           (telemetry/flight.py) exists to prevent. Log it, re-raise,
           or narrow the exception type; genuinely best-effort teardown
           sites (fsync on exotic filesystems, telemetry hooks that must
           never break training) carry a `# jaxlint: disable=JX009`
           pragma stating why. The static twin of the recorder's
           "never lose the traceback" rule.

Suppression: a trailing `# jaxlint: disable=JX00X[,JX00Y]` comment
suppresses those rules on that line (bare `disable` suppresses all);
`# jaxlint: disable-file=JX00X` anywhere suppresses a rule file-wide.

Self-hosting entry point (tier-1 enforced, tests/test_analysis.py):

    python -m deeplearning4j_tpu.analysis.jaxlint [paths...]

exits 0 when the tree is clean, 1 on any violation. The linter itself is
pure stdlib ast/tokenize: it never executes or traces the code it lints,
and never initializes a jax backend (running via -m does import the
package — whose import-time array-freedom is exactly what JX003
enforces).
"""
from __future__ import annotations

import ast
import io
import os
import re
import sys
import tokenize
from typing import Dict, List, Optional, Set, Tuple

from deeplearning4j_tpu.analysis.diagnostics import ERROR, Diagnostic, Report

_ENV_PREFIX = "DL4J_TPU_"
_ENV_EXEMPT_FILE = "envflags.py"

# jax call families that are genuinely dangerous at import time (array
# creation / backend init). Other jax.* calls at module level — custom_vjp,
# jit, tree_util registration — are wrapper-building and stay allowed.
_IMPORT_TIME_BANNED = ("jax.numpy.", "jax.lax.", "jax.random.", "jax.nn.")
_IMPORT_TIME_BANNED_EXACT = {
    "jax.devices", "jax.local_devices", "jax.device_put", "jax.device_get",
    "jax.default_backend", "jax.device_count", "jax.local_device_count",
}

# shape/dtype queries that return plain Python values on tracers — fine
# inside `if` tests even in traced code
_STATIC_QUERIES = {
    "jax.numpy.ndim", "jax.numpy.shape", "jax.numpy.size",
    "jax.numpy.issubdtype", "jax.numpy.result_type", "jax.numpy.isdtype",
    "jax.numpy.dtype", "jax.numpy.iinfo", "jax.numpy.finfo",
}

_PY_RNG_PREFIXES = ("random.", "numpy.random.")

# JX006: files allowed to write model/checkpoint bytes directly — the
# serializer (the payload writer the atomic path wraps) and the atomic
# writer itself
_ATOMIC_WRITER_EXEMPT = ("models/serialization.py", "resilience/checkpoint.py")
# path expressions mentioning any of these read as model/checkpoint
# artifacts (identifier fragments, attribute names, or string constants)
_MODEL_PATH_RE = re.compile(r"model|checkpoint|ckpt|\.zip", re.IGNORECASE)
_NP_SAVERS = {"numpy.save", "numpy.savez", "numpy.savez_compressed"}

# JX016: the one file allowed to compare process_index to a literal —
# it DEFINES the coordinator role the rest of the tree must query
_PROC_ROLE_EXEMPT = ("distributed/runtime.py",)

_SUPPRESS_RE = re.compile(
    r"#\s*jaxlint:\s*(disable(?:-file)?)\s*(?:=\s*([A-Z0-9, ]+))?")


def _traced_dir(path: str) -> bool:
    """ops/ and nn/layers/ hold the jit-traced compute; JX004/JX005 scope."""
    parts = path.replace("\\", "/").split("/")
    if "ops" in parts:
        return True
    return any(a == "nn" and b == "layers"
               for a, b in zip(parts, parts[1:]))


# the dirs whose loops ARE the training/serving hot paths (fit loops,
# SPMD dispatch, worker pumps); JX010 scope
_HOT_LOOP_DIRS = ("models", "parallel", "training", "distributed")


def _hot_loop_dir(path: str) -> bool:
    parts = path.replace("\\", "/").split("/")
    return any(p in _HOT_LOOP_DIRS for p in parts)


# the step-driver call names whose per-iteration execution from a loop
# reimplements the inner fit loop training/engine.py owns; JX015 scope
# is the hot-loop dirs MINUS training/ (the engine and its loop ARE the
# blessed implementation)
_STEP_DRIVERS = ("_dispatch_step", "_dispatch_std", "_fit_std_batch",
                 "_fit_batch_solver", "_fit_tbptt", "iteration_done")


def _step_loop_dir(path: str) -> bool:
    parts = path.replace("\\", "/").split("/")
    return (any(p in ("models", "parallel", "distributed") for p in parts)
            and "training" not in parts)


# the dirs where a thread/queue peer can be a LOST worker (coordinator/
# worker pumps, recovery paths); JX011 scope — an unbounded join/get here
# turns an eviction into a hang
_BLOCKING_WAIT_DIRS = ("distributed", "parallel", "resilience", "serving")


def _blocking_wait_dir(path: str) -> bool:
    parts = path.replace("\\", "/").split("/")
    return any(p in _BLOCKING_WAIT_DIRS for p in parts)


# the dirs whose Event/Condition setters can be a dead dispatcher or a
# shed request's resolver; JX012 scope — an un-timed .wait() here parks
# a serving caller forever
_EVENT_WAIT_DIRS = ("parallel", "serving", "distributed")


def _event_wait_dir(path: str) -> bool:
    parts = path.replace("\\", "/").split("/")
    return any(p in _EVENT_WAIT_DIRS for p in parts)


# the dirs whose retry loops face SHARED resources (checkpoint dirs,
# coordinators, serving queues); JX014 scope — a raw sleep-retry here
# synchronizes a fleet's retries into a thundering herd. retry.py is the
# jittered implementation those loops must route through.
_RETRY_LOOP_DIRS = ("serving", "resilience", "distributed")
_RETRY_LOOP_EXEMPT = ("resilience/retry.py",)
# calls whose presence in the loop mean the delay IS jittered/deadline-
# bounded — the blessed shapes
_BLESSED_BACKOFF = ("decorrelated_backoff", "retry_call",
                    "submit_with_retry")


def _retry_loop_dir(path: str) -> bool:
    parts = path.replace("\\", "/").split("/")
    return any(p in _RETRY_LOOP_DIRS for p in parts)


# JX018: placement policy lives in exactly two files — mesh.py (axes,
# replicated/model shardings) and layout.py (the per-tensor SpecLayout
# + fsdp extension). A PartitionSpec/NamedSharding constructed anywhere
# else in the runtime dirs is a placement the layout module can't audit.
_SPEC_CTOR_DIRS = ("models", "parallel", "training", "distributed")
_SPEC_CTOR_EXEMPT = ("parallel/mesh.py", "parallel/layout.py")
_SPEC_CTORS = {
    "jax.sharding.PartitionSpec", "jax.sharding.NamedSharding",
    "jax.experimental.pjit.PartitionSpec",
    "jax.interpreters.pxla.PartitionSpec",
}


def _spec_ctor_dir(path: str) -> bool:
    parts = path.replace("\\", "/").split("/")
    return any(p in _SPEC_CTOR_DIRS for p in parts)


# JX019: communication is planned by the layout's specs and audited by
# shardlint; a raw collective in model/training/distributed code is
# traffic outside that plan. parallel/ is the collectives' home.
_COLLECTIVE_DIRS = ("models", "training", "distributed")
_RAW_COLLECTIVES = {
    "jax.lax.psum", "jax.lax.pmean", "jax.lax.pmax", "jax.lax.pmin",
    "jax.lax.all_gather", "jax.lax.all_to_all", "jax.lax.ppermute",
    "jax.lax.pshuffle", "jax.lax.psum_scatter",
}


def _collective_dir(path: str) -> bool:
    parts = path.replace("\\", "/").split("/")
    return any(p in _COLLECTIVE_DIRS for p in parts)


# the dirs whose threads appear as lanes in stall reports, trace
# timelines, and lock-inversion flight bundles; JX017 scope — an
# anonymous thread there renders every one of those diagnostics as
# "Thread-12", and a non-daemon one outlives main() on shutdown
_THREAD_CTOR_DIRS = ("serving", "distributed", "telemetry",
                     "resilience", "parallel")


def _thread_ctor_dir(path: str) -> bool:
    parts = path.replace("\\", "/").split("/")
    return any(p in _THREAD_CTOR_DIRS for p in parts)


# the dirs whose buffers sit on the request / telemetry paths; JX020
# scope — an unbounded queue there turns overload into memory growth
# and tail latency instead of a typed shed
_BUFFER_CTOR_DIRS = ("serving", "distributed", "telemetry")

# ctors JX020 audits: (dotted name, bounding kwarg, bounding positional
# index — the arg slot that, when present, bounds the container)
_BOUNDED_BUFFER_CTORS = {
    "queue.Queue": ("maxsize", 0),
    "queue.LifoQueue": ("maxsize", 0),
    "queue.PriorityQueue": ("maxsize", 0),
    "collections.deque": ("maxlen", 1),
}


def _buffer_ctor_dir(path: str) -> bool:
    parts = path.replace("\\", "/").split("/")
    return any(p in _BUFFER_CTOR_DIRS for p in parts)


# the telemetry singletons JX022 protects: a private construction of
# either outside telemetry/ records into an instance no fleet frame is
# ever built from (dotted-suffix match so `telemetry.Tracer`,
# `telemetry.trace.Tracer`, and a bare `from ... import Tracer` alias
# all resolve)
_TELEMETRY_CTOR_SUFFIXES = (
    "telemetry.trace.Tracer",
    "telemetry.Tracer",
    "telemetry.metrics.MetricsRegistry",
    "telemetry.MetricsRegistry",
)


def _telemetry_dir(path: str) -> bool:
    parts = path.replace("\\", "/").split("/")
    return "telemetry" in parts


def _suppressions(source: str) -> Tuple[Dict[int, Optional[Set[str]]],
                                        Set[str]]:
    """Per-line and file-wide rule suppressions from `# jaxlint:` comments.
    A line maps to None when ALL rules are suppressed on it."""
    per_line: Dict[int, Optional[Set[str]]] = {}
    file_wide: Set[str] = set()
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _SUPPRESS_RE.search(tok.string)
            if not m:
                continue
            rules = (set(r.strip() for r in m.group(2).split(","))
                     if m.group(2) else None)
            if m.group(1) == "disable-file":
                # bare disable-file = every rule, mirroring bare disable
                file_wide |= rules if rules is not None else {"*"}
            elif rules is None:
                per_line[tok.start[0]] = None
            else:
                cur = per_line.get(tok.start[0], set())
                per_line[tok.start[0]] = (None if cur is None
                                          else cur | rules)
    except (tokenize.TokenError, IndentationError, SyntaxError):
        # jaxlint: disable=JX009 — ast.parse reports the syntax error
        # as JX000; a second report from the tokenizer would be noise
        pass
    return per_line, file_wide


class _FileLinter(ast.NodeVisitor):
    """One pass over a module: builds the import-alias map up front, then
    visits with context flags (module level vs function body, inside a
    registered vjp-backward function)."""

    def __init__(self, path: str, source: str):
        self.path = path
        self.source = source
        self.findings: List[Diagnostic] = []
        self.aliases: Dict[str, str] = {}
        self.traced = _traced_dir(path)
        self.hot = _hot_loop_dir(path)
        self.steppy = _step_loop_dir(path)
        self.waity = _blocking_wait_dir(path)
        self.eventy = _event_wait_dir(path)
        self.is_envflags = os.path.basename(path) == _ENV_EXEMPT_FILE
        norm = path.replace("\\", "/")
        self.is_atomic_writer = norm.endswith(_ATOMIC_WRITER_EXEMPT)
        self.is_role_definition = norm.endswith(_PROC_ROLE_EXEMPT)
        self.retryish = (_retry_loop_dir(path)
                         and not norm.endswith(_RETRY_LOOP_EXEMPT))
        self.thready = _thread_ctor_dir(path)
        self.buffery = _buffer_ctor_dir(path)
        self.in_telemetry = _telemetry_dir(path)
        self.specy = (_spec_ctor_dir(path)
                      and not norm.endswith(_SPEC_CTOR_EXEMPT))
        self.collectivey = _collective_dir(path)
        self._per_line, self._file_wide = _suppressions(source)
        self._bwd_names: Set[str] = set()
        self._seen: Set[Tuple[str, int, int]] = set()

    # ---- reporting ----
    def _add(self, rule: str, node: ast.AST, message: str) -> None:
        if rule in self._file_wide or "*" in self._file_wide:
            return
        line = getattr(node, "lineno", 0)
        # a trailing pragma anywhere in a multi-line statement's span
        # suppresses findings anchored to its first line
        end = getattr(node, "end_lineno", None) or line
        for ln in range(line, end + 1):
            suppressed = self._per_line.get(ln, set())
            if suppressed is None or rule in suppressed:
                return
        key = (rule, line, getattr(node, "col_offset", 0))
        if key in self._seen:  # nested-function walks revisit subtrees
            return
        self._seen.add(key)
        self.findings.append(Diagnostic(
            rule, ERROR, message,
            f"{self.path}:{line}:{key[2]}"))

    # ---- alias resolution ----
    def _collect_imports(self, tree: ast.Module) -> None:
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.aliases[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.aliases[a.asname or a.name] = (
                        f"{node.module}.{a.name}")

    def _dotted(self, node: ast.AST) -> Optional[str]:
        """Fully-qualified dotted name of an attribute chain, resolved
        through the file's import aliases; None for non-static refs."""
        parts: List[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        base = self.aliases.get(node.id)
        if base is None:
            return None
        parts.append(base)
        return ".".join(reversed(parts))

    # ---- driver ----
    def run(self) -> List[Diagnostic]:
        try:
            tree = ast.parse(self.source, filename=self.path)
        except SyntaxError as e:
            self.findings.append(Diagnostic(
                "JX000", ERROR, f"syntax error: {e.msg}",
                f"{self.path}:{e.lineno or 0}:0"))
            return self.findings
        self._collect_imports(tree)
        self._collect_bwd_names(tree)
        self._collect_wall_clock_names(tree)
        self._collect_gate_names(tree)
        self._check_import_time(tree)
        self._check_retrace_hazards(tree)
        self._check_host_syncs(tree)
        self._check_step_loops(tree)
        self._check_manual_spans(tree)
        self._check_sleep_retry_loops(tree)
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._check_function(node)
            self._check_env_read(node)
            self._check_env_read_indirect(node)
            self._check_raw_model_write(node)
            self._check_wall_duration(node)
            self._check_silent_swallow(node)
            self._check_unbounded_wait(node)
            self._check_unbounded_event_wait(node)
            self._check_process_index_compare(node)
            self._check_thread_ctor(node)
            self._check_unbounded_buffer(node)
            self._check_telemetry_ctor(node)
            self._check_raw_partition_spec(node)
            self._check_raw_collective(node)
        return self.findings

    # ---- JX022: private telemetry instances outside telemetry/ ----
    def _check_telemetry_ctor(self, node: ast.AST) -> None:
        """Flag direct `MetricsRegistry()` / `Tracer()` construction
        outside telemetry/: a private instance records metrics/spans
        that no fleet frame is ever built from — invisible to
        /fleet/metrics, the merged trace, and the federated SLO."""
        if self.in_telemetry or not isinstance(node, ast.Call):
            return
        fn = self._dotted(node.func)
        if fn is None or not fn.endswith(_TELEMETRY_CTOR_SUFFIXES):
            return
        short = fn.rsplit(".", 1)[-1]
        accessor = ("telemetry.trace.tracer()" if short == "Tracer"
                    else "telemetry.metrics.registry()")
        self._add(
            "JX022", node,
            f"private {short}() outside telemetry/: what it records "
            f"never reaches a telemetry frame, so it vanishes from the "
            f"fleet pane (/fleet/metrics, merged trace, federated SLO) "
            f"— use {accessor}, or pragma a deliberate offline instance "
            f"with `# jaxlint: disable=JX022` stating why")

    # ---- JX020: unbounded buffers in the runtime packages ----
    def _check_unbounded_buffer(self, node: ast.AST) -> None:
        """Flag `queue.Queue()`-family ctors without `maxsize=` and
        `collections.deque(...)` without `maxlen=` (or a bounding second
        positional) in serving/, distributed/, telemetry/ — a buffer
        with no bound is a load-shedding decision nobody made."""
        if not self.buffery or not isinstance(node, ast.Call):
            return
        fn = self._dotted(node.func)
        spec = _BOUNDED_BUFFER_CTORS.get(fn)
        if spec is None:
            return
        bound_kwarg, bound_pos = spec
        if any(k.arg == bound_kwarg for k in node.keywords):
            return
        if len(node.args) > bound_pos:
            return  # bound rides in positionally (deque(iterable, n))
        short = fn.rsplit(".", 1)[-1]
        self._add(
            "JX020", node,
            f"unbounded {short}(...) on a runtime path: without "
            f"`{bound_kwarg}=` overload becomes unbounded memory growth "
            f"and tail latency instead of a typed shed — bound it, or "
            f"pragma a buffer whose bound is enforced elsewhere with "
            f"`# jaxlint: disable=JX020` stating why")

    # ---- JX019: raw collectives outside the parallel package ----
    def _check_raw_collective(self, node: ast.AST) -> None:
        """Flag `jax.lax.psum`-family calls in models/, training/, or
        distributed/ — communication the layout's plan (and shardlint's
        static audit of it) cannot see."""
        if not self.collectivey or not isinstance(node, ast.Call):
            return
        fn = self._dotted(node.func)
        if fn not in _RAW_COLLECTIVES:
            return
        name = fn.rsplit(".", 1)[-1]
        self._add(
            "JX019", node,
            f"raw jax.lax.{name}(...) outside the parallel package: "
            f"collectives are the communication plan shardlint audits "
            f"from the layout's specs — route through parallel/ "
            f"(mesh/layout/wrapper seams), or pragma a genuinely local "
            f"collective with `# jaxlint: disable=JX019` stating why")

    # ---- JX018: raw PartitionSpec/NamedSharding outside layout ----
    def _check_raw_partition_spec(self, node: ast.AST) -> None:
        """Flag sharding-spec construction in the runtime dirs outside
        parallel/mesh.py + parallel/layout.py — placement policy the
        SpecLayout/fsdp machinery can't see or keep consistent."""
        if not self.specy or not isinstance(node, ast.Call):
            return
        fn = self._dotted(node.func)
        if fn not in _SPEC_CTORS:
            return
        kind = fn.rsplit(".", 1)[-1]
        self._add(
            "JX018", node,
            f"raw {kind}(...) outside parallel/mesh.py + "
            f"parallel/layout.py: placement policy belongs to the "
            f"SpecLayout module (fsdp gather/scatter seams audit specs "
            f"they can see) — route through mesh.py/layout.py helpers, "
            f"or pragma a genuinely local spec with "
            f"`# jaxlint: disable=JX018` stating why")

    # ---- JX017: anonymous/non-daemon threads in runtime packages ----
    def _check_thread_ctor(self, node: ast.AST) -> None:
        """Flag `threading.Thread(...)` in the runtime dirs that lacks a
        `name=` (diagnostics identify lanes by thread name) or lacks
        `daemon=True` (a forgotten non-daemon thread wedges interpreter
        shutdown). `daemon=<non-constant>` passes — the value is a
        runtime decision the linter can't judge."""
        if not self.thready or not isinstance(node, ast.Call):
            return
        if self._dotted(node.func) != "threading.Thread":
            return
        kwargs = {k.arg: k.value for k in node.keywords if k.arg}
        missing = []
        if "name" not in kwargs:
            missing.append("name=<lane name>")
        daemon = kwargs.get("daemon")
        if daemon is None or (isinstance(daemon, ast.Constant)
                              and daemon.value is False):
            missing.append("daemon=True")
        if missing:
            self._add(
                "JX017", node,
                f"runtime thread constructed without "
                f"{' and '.join(missing)} — stall reports, trace lanes "
                f"and lock-inversion bundles identify threads by name "
                f"(an anonymous 'Thread-12' is undebuggable), and a "
                f"non-daemon thread left running wedges interpreter "
                f"shutdown; a lifecycle-managed thread (joined before "
                f"exit, or deliberately non-daemon) carries a "
                f"`# jaxlint: disable=JX017` pragma stating why")

    # ---- JX016: literal coordinator-role comparisons ----
    def _check_process_index_compare(self, node: ast.AST) -> None:
        """Flag `jax.process_index() <op> <int literal>` (either order)
        anywhere outside distributed/runtime.py — the coordinator role
        must be queried (`runtime_info().is_coordinator`), not re-derived
        from a magic rank."""
        if self.is_role_definition or not isinstance(node, ast.Compare):
            return
        sides = [node.left, *node.comparators]

        def is_proc_index(n: ast.AST) -> bool:
            return (isinstance(n, ast.Call)
                    and self._dotted(n.func) == "jax.process_index")

        def is_int_literal(n: ast.AST) -> bool:
            return (isinstance(n, ast.Constant)
                    and type(n.value) is int)

        if (any(is_proc_index(s) for s in sides)
                and any(is_int_literal(s) for s in sides)):
            self._add(
                "JX016", node,
                "literal comparison of jax.process_index() — the "
                "coordinator role is defined ONCE by "
                "distributed.runtime.runtime_info().is_coordinator "
                "(the property the multihost membership and chaos "
                "layers key on); query it instead of re-deriving the "
                "role from a magic rank, or pragma a reasoned literal "
                "check with `# jaxlint: disable=JX016`")

    # ---- JX011: unbounded join/get in cluster-facing dirs ----
    _WAIT_METHODS = ("join", "get")

    def _check_unbounded_wait(self, node: ast.AST) -> None:
        """A zero-argument `.join()` / `.get()` blocks forever. The
        heuristic is exact for threads/queues: `str.join` and `dict.get`
        REQUIRE an argument, so an argument-less call can only be a
        blocking wait — and in distributed/parallel/resilience code the
        peer being waited on can be an evicted worker."""
        if not self.waity or not isinstance(node, ast.Call):
            return
        if not (isinstance(node.func, ast.Attribute)
                and node.func.attr in self._WAIT_METHODS):
            return
        if node.args or node.keywords:
            # ANY argument disqualifies: a positional/keyword timeout
            # bounds the wait, and other kwargs (q.get(block=False),
            # str.join's iterable) mean this isn't the bare blocking form
            return
        self._add(
            "JX011", node,
            f"unbounded '.{node.func.attr}()' — an evicted or hung worker "
            f"on the other side makes the coordinator wait forever "
            f"(distributed/membership.py evicts on missed heartbeats; this "
            f"call would never return to notice). Join/get in bounded "
            f"slices or pass a timeout; pragma a reasoned infinite wait "
            f"with `# jaxlint: disable=JX011`")

    # ---- JX012: unbounded Event/Condition wait in serving dirs ----
    def _check_unbounded_event_wait(self, node: ast.AST) -> None:
        """A zero-argument `.wait()` blocks until someone calls set()/
        notify() — and in parallel/serving/distributed code that someone
        can be a crashed dispatcher. Any argument (a timeout) bounds the
        wait and passes. Module-level functions that spell `.wait`
        (`os.wait()`) resolve through the import-alias map and are
        skipped: an Event/Condition is always held in a variable, which
        does not resolve."""
        if not self.eventy or not isinstance(node, ast.Call):
            return
        if not (isinstance(node.func, ast.Attribute)
                and node.func.attr == "wait"):
            return
        if node.args or node.keywords:
            return
        if self._dotted(node.func) is not None:
            return  # a module function like os.wait(), not an object wait
        self._add(
            "JX012", node,
            f"unbounded '.wait()' — if the thread that would set/notify "
            f"this event dies (crashed dispatcher, shed request, evicted "
            f"worker), the caller hangs forever. Wait in bounded slices "
            f"(`ev.wait(0.05)` in a loop re-checking liveness, the "
            f"serving runtime's drain contract); pragma a reasoned "
            f"infinite wait with `# jaxlint: disable=JX012`")

    # ---- JX013: manually-opened trace spans ----
    _SPAN_OPENERS = ("span", "start_span")

    def _check_manual_spans(self, tree: ast.Module) -> None:
        """Flag `.span(...)` calls whose result escapes the managed
        forms. First pass collects the call nodes that ARE managed —
        `with`-item context expressions, `enter_context(...)` arguments,
        `return` values (the caller manages) — then every remaining
        span-opening call is a manual open with no guaranteed finish."""
        managed: Set[int] = set()
        for node in ast.walk(tree):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    managed.add(id(item.context_expr))
            elif isinstance(node, ast.Return) and node.value is not None:
                managed.add(id(node.value))
            elif (isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Attribute)
                  and node.func.attr == "enter_context"):
                for a in node.args:
                    managed.add(id(a))
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in self._SPAN_OPENERS):
                continue
            if id(node) in managed:
                continue
            self._add(
                "JX013", node,
                f"'.{node.func.attr}(...)' opened outside a `with` (or "
                f"enter_context/return) — a manually-entered span can "
                f"miss its finish on an exception path, leaking its "
                f"attached TraceContext onto the thread so later spans "
                f"parent under a dead request "
                f"(telemetry/context.py's handoff contract); use "
                f"`with tracer().span(...)` / the @traced decorator, or "
                f"pragma a reasoned manual site with "
                f"`# jaxlint: disable=JX013`")

    # ---- JX014: hand-rolled sleep-retry loops ----
    def _check_sleep_retry_loops(self, tree: ast.Module) -> None:
        """Flag `time.sleep(...)` calls lexically inside a For/While
        whose subtree also holds an `except` handler — the
        catch-sleep-retry shape — unless the same loop routes its delay
        through a blessed backoff (`decorrelated_backoff`/`retry_call`/
        `submit_with_retry`). Innermost qualifying loop wins; function
        bodies defined inside a loop run at call time and are walked as
        their own (non-loop) scope."""
        if not self.retryish:
            return
        for loop in ast.walk(tree):
            if not isinstance(loop, (ast.For, ast.AsyncFor, ast.While)):
                continue
            sleeps: List[ast.Call] = []
            has_except = blessed = False
            stack: List[ast.AST] = list(ast.iter_child_nodes(loop))
            while stack:
                n = stack.pop()
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                    continue
                if isinstance(n, ast.ExceptHandler):
                    has_except = True
                elif isinstance(n, ast.Call):
                    fn = self._dotted(n.func)
                    if fn == "time.sleep":
                        sleeps.append(n)
                    else:
                        name = (n.func.attr
                                if isinstance(n.func, ast.Attribute)
                                else n.func.id
                                if isinstance(n.func, ast.Name) else "")
                        if name in _BLESSED_BACKOFF:
                            blessed = True
                stack.extend(ast.iter_child_nodes(n))
            if not (has_except and sleeps) or blessed:
                continue
            for call in sleeps:
                self._add(
                    "JX014", call,
                    "raw 'time.sleep(...)' in a catch-and-retry loop — "
                    "a fixed/hand-rolled delay retries a failed fleet in "
                    "lockstep and thundering-herds the shared resource "
                    "(coordinator, checkpoint dir, serving queue); "
                    "derive the delay via resilience.retry."
                    "decorrelated_backoff / retry_call (or use "
                    "serving.submit_with_retry, which also honors "
                    "retry_after_s hints), or pragma a reasoned "
                    "fixed-cadence wait with `# jaxlint: disable=JX014`")

    # ---- JX009: silent except/pass swallow ----
    def _check_silent_swallow(self, node: ast.AST) -> None:
        if not isinstance(node, ast.ExceptHandler):
            return
        if len(node.body) == 1 and isinstance(node.body[0], ast.Pass):
            what = ("bare except" if node.type is None
                    else f"except {ast.unparse(node.type)}")
            self._add(
                "JX009", node,
                f"silent `{what}: pass` — the exception and its traceback "
                f"vanish (the failure mode the flight recorder exists to "
                f"prevent); log it, re-raise, or narrow the type — "
                f"pragma genuinely best-effort teardown sites with "
                f"`# jaxlint: disable=JX009`")

    # ---- JX001: raw env gates ----
    def _check_env_read(self, node: ast.AST) -> None:
        if self.is_envflags:
            return
        name = None
        if isinstance(node, ast.Call):
            fn = self._dotted(node.func)
            if fn in ("os.environ.get", "os.getenv") and node.args:
                arg = node.args[0]
                if (isinstance(arg, ast.Constant)
                        and isinstance(arg.value, str)
                        and arg.value.startswith(_ENV_PREFIX)):
                    name = arg.value
        elif (isinstance(node, ast.Subscript)
              and isinstance(node.ctx, ast.Load)
              and self._dotted(node.value) == "os.environ"
              and isinstance(node.slice, ast.Constant)
              and isinstance(node.slice.value, str)
              and node.slice.value.startswith(_ENV_PREFIX)):
            name = node.slice.value
        if name is not None:
            self._add("JX001", node,
                      f"raw os.environ read of '{name}' — all DL4J_TPU_* "
                      f"gates parse through util.envflags (one normalized "
                      f"truthy/falsy spelling set)")

    # ---- JX021: laundered env-gate reads ----
    def _collect_gate_names(self, tree: ast.Module) -> None:
        """Names/attributes assigned a DL4J_TPU_* string literal anywhere
        in the file (`GATE = "DL4J_TPU_X"`, `self.gate = "DL4J_TPU_X"`):
        passing one to os.environ later is the indirected form of the
        JX001 defect. File-wide by design, like JX007's wall-clock names —
        the constant typically sits at module top, the read in a method."""
        self._gate_names: Dict[str, str] = {}
        for node in ast.walk(tree):
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                value, targets = node.value, list(node.targets)
            elif isinstance(node, ast.AnnAssign):
                value, targets = node.value, [node.target]
            else:
                continue
            if not (isinstance(value, ast.Constant)
                    and isinstance(value.value, str)
                    and value.value.startswith(_ENV_PREFIX)):
                continue
            for t in targets:
                if isinstance(t, ast.Name):
                    self._gate_names[t.id] = value.value
                elif isinstance(t, ast.Attribute):
                    self._gate_names[t.attr] = value.value

    def _gate_operand(self, node: ast.AST) -> Optional[Tuple[str, bool]]:
        """(gate name, was_literal) when the expression is a DL4J_TPU_*
        gate — a string literal or a tracked assigned name; else None."""
        if (isinstance(node, ast.Constant) and isinstance(node.value, str)
                and node.value.startswith(_ENV_PREFIX)):
            return node.value, True
        if isinstance(node, ast.Name) and node.id in self._gate_names:
            return self._gate_names[node.id], False
        if isinstance(node, ast.Attribute) and node.attr in self._gate_names:
            return self._gate_names[node.attr], False
        return None

    def _check_env_read_indirect(self, node: ast.AST) -> None:
        if self.is_envflags:
            return
        hit: Optional[Tuple[str, bool, str]] = None  # gate, literal, form
        if isinstance(node, ast.Call):
            fn = self._dotted(node.func)
            if fn in ("os.environ.get", "os.getenv", "os.environ.pop",
                      "os.environ.setdefault") and node.args:
                got = self._gate_operand(node.args[0])
                # literal get/getenv is JX001's report; JX021 owns the
                # indirected form plus the read-modify calls JX001 never
                # matched
                if got and (not got[1]
                            or fn in ("os.environ.pop",
                                      "os.environ.setdefault")):
                    hit = (got[0], got[1], f"{fn}(...)")
        elif (isinstance(node, ast.Subscript)
              and isinstance(node.ctx, ast.Load)
              and self._dotted(node.value) == "os.environ"):
            got = self._gate_operand(node.slice)
            if got and not got[1]:  # literal subscript is JX001's
                hit = (got[0], got[1], "os.environ[...]")
        elif isinstance(node, ast.Compare) and len(node.ops) == 1 \
                and isinstance(node.ops[0], (ast.In, ast.NotIn)) \
                and self._dotted(node.comparators[0]) == "os.environ":
            got = self._gate_operand(node.left)
            if got:
                hit = (got[0], got[1], "'... in os.environ'")
        if hit is None:
            return
        gate, literal, form = hit
        via = "" if literal else " via an assigned name"
        self._add(
            "JX021", node,
            f"laundered os.environ read of '{gate}'{via} ({form}) — "
            f"indirection does not exempt a DL4J_TPU_* gate from the "
            f"one normalized parse (util.envflags), and a raw read "
            f"also skips the tuner's live-override overlay; route it "
            f"through envflags or pragma a reasoned site with "
            f"`# jaxlint: disable=JX021`")

    # ---- JX006: raw model/checkpoint writes ----
    @staticmethod
    def _mode_arg(node: ast.Call, pos: int) -> Optional[str]:
        """The constant mode string of an open()/ZipFile() call (positional
        slot `pos` or `mode=` keyword); None when absent or dynamic."""
        if (len(node.args) > pos
                and isinstance(node.args[pos], ast.Constant)
                and isinstance(node.args[pos].value, str)):
            return node.args[pos].value
        for kw in node.keywords:
            if (kw.arg == "mode" and isinstance(kw.value, ast.Constant)
                    and isinstance(kw.value.value, str)):
                return kw.value.value
        return None

    @staticmethod
    def _mentions_model_path(expr: ast.AST) -> bool:
        """Heuristic: the path expression textually references a model/
        checkpoint artifact (identifier fragments, attribute names, or
        string constants matching model|checkpoint|ckpt|.zip)."""
        parts: List[str] = []
        for n in ast.walk(expr):
            if isinstance(n, ast.Name):
                parts.append(n.id)
            elif isinstance(n, ast.Attribute):
                parts.append(n.attr)
            elif isinstance(n, ast.Constant) and isinstance(n.value, str):
                parts.append(n.value)
        return bool(_MODEL_PATH_RE.search(" ".join(parts)))

    def _check_raw_model_write(self, node: ast.AST) -> None:
        if self.is_atomic_writer or not isinstance(node, ast.Call):
            return
        target: Optional[ast.AST] = None
        kind = ""
        if isinstance(node.func, ast.Name) and node.func.id == "open":
            mode = self._mode_arg(node, 1)
            if (mode and "b" in mode and any(c in mode for c in "wxa")
                    and node.args):
                target, kind = node.args[0], f"open(..., {mode!r})"
        else:
            fn = self._dotted(node.func)
            if fn in _NP_SAVERS and node.args:
                target, kind = node.args[0], f"{fn}(...)"
            elif fn == "zipfile.ZipFile" and node.args:
                mode = self._mode_arg(node, 1)
                if mode and mode[:1] in "wxa":
                    target = node.args[0]
                    kind = f"zipfile.ZipFile(..., {mode!r})"
        if target is not None and self._mentions_model_path(target):
            self._add(
                "JX006", node,
                f"raw {kind} write to a model/checkpoint path — a crash "
                f"mid-write tears the artifact; route through the atomic "
                f"writer (resilience.checkpoint.atomic_write_model / "
                f"CheckpointManager)")

    # ---- JX007: wall-clock durations ----
    def _is_wall_clock_call(self, node: ast.AST) -> bool:
        return (isinstance(node, ast.Call)
                and self._dotted(node.func) == "time.time")

    def _collect_wall_clock_names(self, tree: ast.Module) -> None:
        """Names/attributes assigned from time.time() anywhere in the file
        (`t0 = time.time()`, `self.start = time.time()`): subtracting one
        of them later is the cross-statement form of the defect. File-wide
        by design — the assignment is typically in __init__, the
        subtraction in a callback."""
        self._wall_names: Set[str] = set()
        for node in ast.walk(tree):
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                value, targets = node.value, list(node.targets)
            elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
                value, targets = node.value, [node.target]
            else:
                continue
            if value is None or not self._is_wall_clock_call(value):
                continue
            for t in targets:
                if isinstance(t, ast.Name):
                    self._wall_names.add(t.id)
                elif isinstance(t, ast.Attribute):
                    self._wall_names.add(t.attr)

    def _is_wall_clock_operand(self, node: ast.AST) -> bool:
        if self._is_wall_clock_call(node):
            return True
        if isinstance(node, ast.Name):
            return node.id in self._wall_names
        if isinstance(node, ast.Attribute):
            return node.attr in self._wall_names
        return False

    def _check_wall_duration(self, node: ast.AST) -> None:
        if not (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub)):
            return
        for side in (node.left, node.right):
            if self._is_wall_clock_operand(side):
                self._add(
                    "JX007", node,
                    "duration computed by subtracting time.time() values — "
                    "wall clock steps under NTP and corrupts "
                    "timelines/ETAs; use time.perf_counter() (or "
                    "time.monotonic()) for durations, keep time.time() "
                    "for pure timestamps")
                return

    # ---- JX008: retrace hazards ----
    _JIT_WRAPPERS = ("jax.jit", "jax.pmap")

    def _is_jit_wrap(self, node: ast.AST) -> Optional[str]:
        """The dotted name when `node` is a call that CREATES a jit/pmap
        wrapper: jax.jit(...), jax.pmap(...), the jaxcompat.jit seam, or
        functools.partial(jax.jit, ...)."""
        if not isinstance(node, ast.Call):
            return None
        fn = self._dotted(node.func)
        if fn in self._JIT_WRAPPERS or (fn and fn.endswith("jaxcompat.jit")):
            return fn
        if fn == "functools.partial" and node.args:
            inner = self._dotted(node.args[0])
            if inner in self._JIT_WRAPPERS:
                return inner
        return None

    def _check_retrace_hazards(self, tree: ast.Module) -> None:
        """Walk with loop-ancestry: a jit wrapper created inside a
        For/While body retraces every iteration. Function/lambda bodies
        reset the flag (they run at call time, not per loop iteration) —
        but their DECORATORS evaluate in the loop and stay flagged.
        `jax.jit(f)(x)` immediate invocation is flagged anywhere."""
        stack = [(n, False) for n in ast.iter_child_nodes(tree)]
        while stack:
            node, in_loop = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for d in node.decorator_list:
                    fn = self._is_jit_wrap(d) or (
                        self._dotted(d) if self._dotted(d)
                        in self._JIT_WRAPPERS else None)
                    if fn and in_loop:
                        self._add(
                            "JX008", d,
                            f"'{fn}' wrapper created inside a loop — "
                            f"each iteration builds a fresh wrapper with "
                            f"an empty trace cache (recompiles every "
                            f"time); hoist the jitted function out of "
                            f"the loop")
                stack.extend((c, False) for c in ast.iter_child_nodes(node))
                continue
            if isinstance(node, ast.Lambda):
                stack.extend((c, False) for c in ast.iter_child_nodes(node))
                continue
            if isinstance(node, ast.Call):
                inner = self._is_jit_wrap(node.func)
                if inner is not None:
                    self._add(
                        "JX008", node,
                        f"'{inner}(...)(...)' immediate invocation — the "
                        f"wrapper and its compile cache are discarded "
                        f"after one call, so every call of the enclosing "
                        f"function retraces; bind the jitted function "
                        f"once and reuse it")
                elif in_loop and self._is_jit_wrap(node):
                    self._add(
                        "JX008", node,
                        f"'{self._is_jit_wrap(node)}' wrapper created "
                        f"inside a loop — each iteration builds a fresh "
                        f"wrapper with an empty trace cache (recompiles "
                        f"every time); hoist the jitted function out of "
                        f"the loop")
            here_loop = in_loop or isinstance(
                node, (ast.For, ast.AsyncFor, ast.While))
            stack.extend((c, here_loop) for c in ast.iter_child_nodes(node))

    # ---- JX010: per-step host syncs in hot loops ----
    _SYNC_METHODS = ("item", "block_until_ready")

    def _check_host_syncs(self, tree: ast.Module) -> None:
        """Walk with loop-ancestry (the JX008 walker's shape): a device
        sync INSIDE a For/While body in a hot-loop dir stalls the
        dispatch pipeline every iteration. Function/lambda bodies reset
        the flag — a helper defined in a loop runs at call time."""
        if not self.hot:
            return
        stack = [(n, False) for n in ast.iter_child_nodes(tree)]
        while stack:
            node, in_loop = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                stack.extend((c, False) for c in ast.iter_child_nodes(node))
                continue
            if in_loop and isinstance(node, ast.Call):
                self._host_sync_call(node)
            here = in_loop or isinstance(node,
                                         (ast.For, ast.AsyncFor, ast.While))
            stack.extend((c, here) for c in ast.iter_child_nodes(node))

    # ---- JX015: reimplemented inner step loop ----
    def _check_step_loops(self, tree: ast.Module) -> None:
        """Walk with loop-ancestry, tracking the enclosing For targets:
        a step-driver call (`net._fit_tbptt(ds)`, a by-hand
        `lst.iteration_done(...)`) inside a For/While body outside
        training/engine.py is a private inner fit loop. The one blessed
        per-STEP shape is exempt by receiver: `for lst in listeners:
        lst.iteration_done(...)` iterates LISTENERS for one step (the
        receiver IS the loop variable), while a step loop iterates
        BATCHES (`for ds in shard: net._fit_tbptt(ds)` — the receiver is
        not). Function/lambda bodies reset the ancestry — a callback
        defined in a loop runs at call time."""
        if not self.steppy:
            return
        stack = [(n, False, frozenset()) for n in ast.iter_child_nodes(tree)]
        while stack:
            node, in_loop, targets = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                stack.extend((c, False, frozenset())
                             for c in ast.iter_child_nodes(node))
                continue
            if (in_loop and isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _STEP_DRIVERS
                    and not (isinstance(node.func.value, ast.Name)
                             and node.func.value.id in targets)):
                self._add(
                    "JX015", node,
                    f"'.{node.func.attr}(...)' driven per-iteration from "
                    f"a loop outside training/engine.py — a private inner "
                    f"step loop opts out of the engine's attachments "
                    f"(window gate, etl/step spans, watchdog beats, "
                    f"sentry window hooks); route it through "
                    f"WindowedFitLoop (model._engine_loop() / "
                    f"engine.run_partition), or pragma a reasoned "
                    f"private loop with `# jaxlint: disable=JX015`")
            here = in_loop or isinstance(node,
                                         (ast.For, ast.AsyncFor, ast.While))
            here_targets = targets
            if isinstance(node, (ast.For, ast.AsyncFor)):
                names = [n.id for n in ast.walk(node.target)
                         if isinstance(n, ast.Name)]
                here_targets = targets | frozenset(names)
            stack.extend((c, here, here_targets)
                         for c in ast.iter_child_nodes(node))

    def _host_sync_call(self, node: ast.Call) -> None:
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in self._SYNC_METHODS
                and not node.args):
            self._add(
                "JX010", node,
                f"'.{node.func.attr}()' inside a hot loop — a device->"
                f"host sync every iteration stalls the dispatch "
                f"pipeline; batch the fetch once per window "
                f"(training/engine.py) or hoist it out of the loop")
            return
        what = None
        if (isinstance(node.func, ast.Name) and node.func.id == "float"):
            what = "float(...)"
        else:
            fn = self._dotted(node.func)
            if fn == "numpy.asarray":
                what = "np.asarray(...)"
            elif fn == "jax.device_get":
                # the masters' historical split-loop spelling of the
                # same per-step fetch tax
                what = "jax.device_get(...)"
        if (what and len(node.args) == 1
                and isinstance(node.args[0], ast.Name)):
            self._add(
                "JX010", node,
                f"'{what}' on '{node.args[0].id}' inside a hot loop — "
                f"fetching a device value per step serializes host and "
                f"device (the per-step score-sync tax); fetch once per "
                f"window (training/engine.py's rule) or pragma a "
                f"legitimate boundary site with "
                f"`# jaxlint: disable=JX010`")

    # ---- JX002: custom_vjp cotangents ----
    def _collect_bwd_names(self, tree: ast.Module) -> None:
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "defvjp"
                    and len(node.args) >= 2
                    and isinstance(node.args[1], ast.Name)):
                self._bwd_names.add(node.args[1].id)

    # ---- JX003: import-time jax compute ----
    def _iter_import_time(self, tree: ast.Module):
        """Nodes that execute at import: everything except function/lambda
        BODIES — but decorators and default-arg expressions DO run."""
        stack: List[ast.AST] = list(tree.body)
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                stack.extend(n.decorator_list)
                stack.extend(d for d in n.args.defaults if d is not None)
                stack.extend(d for d in n.args.kw_defaults if d is not None)
                continue
            if isinstance(n, ast.Lambda):
                # the body runs at call time, but defaults run at import
                stack.extend(d for d in n.args.defaults if d is not None)
                stack.extend(d for d in n.args.kw_defaults if d is not None)
                continue
            yield n
            stack.extend(ast.iter_child_nodes(n))

    def _check_import_time(self, tree: ast.Module) -> None:
        for node in self._iter_import_time(tree):
            if isinstance(node, ast.Call):
                fn = self._dotted(node.func)
                if fn and (fn.startswith(_IMPORT_TIME_BANNED)
                           or fn in _IMPORT_TIME_BANNED_EXACT):
                    self._add(
                        "JX003", node,
                        f"'{fn}(...)' runs at module import time — imports "
                        f"must stay array-free (move it inside a function "
                        f"or precompute a Python constant)")

    # ---- function-body rules: JX002 / JX004 / JX005 ----
    def _check_function(self, fn: ast.FunctionDef) -> None:
        if fn.name in self._bwd_names:
            for node in ast.walk(fn):
                if (isinstance(node, ast.Call)
                        and self._dotted(node.func) == "jax.numpy.zeros_like"):
                    self._add(
                        "JX002", node,
                        f"'{fn.name}' is a defvjp backward rule: "
                        f"jnp.zeros_like makes a wrong-dtype cotangent for "
                        f"integer primals — use "
                        f"util.cotangent.zeros_cotangent")
        if not self.traced:
            return
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                dn = self._dotted(node.func)
                if dn and dn.startswith(_PY_RNG_PREFIXES):
                    self._add(
                        "JX004", node,
                        f"Python-level RNG '{dn}' inside traced code — "
                        f"invisible to jit (frozen into the trace); thread "
                        f"a jax.random key instead")
            elif isinstance(node, (ast.If, ast.While, ast.IfExp)):
                self._check_traced_branch(node.test)

    def _check_traced_branch(self, test: ast.AST) -> None:
        for node in ast.walk(test):
            if not isinstance(node, ast.Call):
                continue
            dn = self._dotted(node.func)
            if (dn and dn.startswith(("jax.numpy.", "jax.lax."))
                    and dn not in _STATIC_QUERIES):
                self._add(
                    "JX005", node,
                    f"Python branch on '{dn}(...)' — a traced array in an "
                    f"`if`/`while` test raises under jit; use lax.cond / "
                    f"jnp.where")


# ---------------------------------------------------------------------------
# API + CLI
# ---------------------------------------------------------------------------


def lint_source(source: str, path: str = "<string>") -> List[Diagnostic]:
    """Lint one module's source text (unit-test surface)."""
    return _FileLinter(path, source).run()


def _package_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def iter_py_files(paths: List[str]):
    for p in paths:
        if os.path.isfile(p):
            yield p
        else:
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs if d != "__pycache__")
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(root, f)


def lint_paths(paths: Optional[List[str]] = None) -> Report:
    """Lint files/directories (default: the installed package tree)."""
    paths = paths or [_package_root()]
    rep = Report()
    for path in iter_py_files(paths):
        try:
            with open(path, "r", encoding="utf-8") as fh:
                source = fh.read()
        except OSError as e:
            rep.add("JX000", ERROR, f"unreadable: {e}", path)
            continue
        rep.diagnostics.extend(lint_source(source, path))
    return rep


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    quiet = "-q" in argv
    paths = [a for a in argv if not a.startswith("-")]
    rep = lint_paths(paths or None)
    for d in rep.sorted():
        print(d)
    if not quiet:
        n = len(rep.diagnostics)
        print(f"jaxlint: {n} finding(s)" if n else "jaxlint: clean")
    return 1 if rep.diagnostics else 0


if __name__ == "__main__":
    sys.exit(main())
