"""Single normalized parser for `DL4J_TPU_*` environment gates — and, as
of the self-tuning runtime (docs/TUNING.md), the typed KNOB REGISTRY the
closed-loop tuner writes through.

Every boolean env gate in the framework reads through this module so all
gates share ONE truthy/falsy spelling set (ADVICE.md round 5: the
`DL4J_TPU_PALLAS_XENT` parse drifted from `lstm_helper_mode`'s — 'False',
'no', ' 0 ' counted as enabled on one gate and disabled on another).
The jaxlint rules JX001/JX021 (`analysis/jaxlint.py`) enforce the
contract statically: any raw `os.environ` read of a `DL4J_TPU_*` name
outside this module is a lint error — a raw read would also silently
bypass the tuner's override overlay below.

Spelling contract (case-insensitive, whitespace-stripped):
    truthy:  1, true, yes, on
    falsy:   everything else that is SET (0, false, no, off, "", garbage)
    unset:   the variable is absent -> caller's default applies

Garbage deliberately reads as falsy, never as enabled: a typo'd gate must
not silently switch an accelerator code path on (the
`lstm_helper_mode` precedent).

Knob registry
-------------
Every `DL4J_TPU_*` gate is DECLARED once in `KNOBS` with its type,
default, range and mutability. Declarations are documentation-grade
metadata (`cli config` renders them, flight bundles and profile reports
stamp them) — reads never require one, so an undeclared experimental
gate still parses. Mutability separates:

    static  read at import/construction time, or anywhere a mid-run
            flip would tear state (cache dirs, mesh shapes, gates that
            allocate singletons). The tuner may NOT override these.
    live    re-read on a boundary that makes a flip safe (epoch start,
            iterator reset, scrape tick). The tuner steers these via
            `set_override` — an in-process overlay consulted by every
            read BEFORE the environment, so all existing call sites see
            tuner decisions with zero wiring.

`effective(name)` -> (value, provenance) where provenance is one of
``tuner | env | default`` — the attribution surface `cli config`,
`/profile` and flight bundles share.
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

# the only spellings that ENABLE a gate; everything else set is falsy
# (the canonical falsy spellings are 0/false/no/off/"", but garbage reads
# as falsy too — see the module docstring)
TRUTHY = frozenset({"1", "true", "yes", "on"})

# knob mutability classes (module docstring)
STATIC = "static"
LIVE = "live"

# provenance values returned by `effective`
PROV_TUNER = "tuner"
PROV_ENV = "env"
PROV_DEFAULT = "default"

# ---------------------------------------------------------------------------
# the tuner's override overlay
# ---------------------------------------------------------------------------
# name -> raw string value, consulted by value()/flag() BEFORE os.environ.
# Plain dict + lock: the hot-path read is one truthiness check on an
# (almost always) empty dict, so gate-off fit loops pay nothing.
_overrides: Dict[str, str] = {}
_overrides_lock = threading.Lock()


def value(name: str, default: Optional[str] = None) -> Optional[str]:
    """Raw string value, whitespace-stripped; `default` when unset.
    Tuner overrides (set_override) take precedence over the
    environment."""
    if _overrides:
        ov = _overrides.get(name)
        if ov is not None:
            return ov
    env = os.environ.get(name)
    return default if env is None else env.strip()


def flag(name: str) -> Optional[bool]:
    """Tri-state boolean: True for a recognised truthy spelling, False for
    anything else that is set, None when the variable is unset."""
    env = value(name)
    if env is None:
        return None
    return env.lower() in TRUTHY


def enabled(name: str, default: bool = False) -> bool:
    """Two-state boolean: `default` when unset, else the normalized flag."""
    f = flag(name)
    return default if f is None else f


def int_value(name: str, default: int) -> int:
    """Integer gate with the module's garbage-tolerance contract: unset,
    empty, or unparsable values read as `default` — a typo'd gate must
    never crash the (often failure-recovery) code path reading it."""
    raw = value(name)
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        return default


def float_value(name: str, default: float) -> float:
    """Float gate; same garbage-tolerance contract as int_value."""
    raw = value(name)
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        return default


def mode(name: str, when_true: str = "forced", when_false: str = "off",
         when_unset: str = "auto") -> str:
    """Tri-state gates mapped to mode strings (`lstm_helper_mode` shape):
    truthy spelling -> `when_true`, any other set value -> `when_false`,
    unset -> `when_unset`."""
    f = flag(name)
    if f is None:
        return when_unset
    return when_true if f else when_false


# ---------------------------------------------------------------------------
# typed knob registry
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Knob:
    """One declared `DL4J_TPU_*` gate: the registry row `cli config`
    renders and `set_override` validates against."""

    name: str
    kind: str               # bool | int | float | str
    default: Any
    help: str = ""
    lo: Optional[float] = None   # inclusive range for int/float knobs
    hi: Optional[float] = None
    mutability: str = STATIC

    def coerce(self, raw: Any) -> Any:
        """Parse + range-clamp a candidate override value; raises
        ValueError on type mismatch (overrides are tuner-set, so unlike
        env reads they FAIL LOUD — a typed controller writing garbage is
        a bug, not operator input)."""
        if self.kind == "bool":
            if isinstance(raw, bool):
                return raw
            return str(raw).strip().lower() in TRUTHY
        if self.kind == "int":
            v: Any = int(raw)
        elif self.kind == "float":
            v = float(raw)
        else:
            return str(raw)
        if self.lo is not None:
            v = max(v, type(v)(self.lo))
        if self.hi is not None:
            v = min(v, type(v)(self.hi))
        return v


KNOBS: Dict[str, Knob] = {}


def _declare(name: str, kind: str, default: Any, help: str = "", *,
             lo: Optional[float] = None, hi: Optional[float] = None,
             mutability: str = STATIC) -> None:
    KNOBS[name] = Knob(name, kind, default, help, lo, hi, mutability)


# --- execution / engine ----------------------------------------------------
_declare("DL4J_TPU_STEP_WINDOW", "int", 1,
         "Steps rolled into one jitted lax.scan dispatch (K); re-read at "
         "each epoch start, so the tuner can re-key the window live",
         lo=1, hi=64, mutability=LIVE)
_declare("DL4J_TPU_PREFETCH_DEPTH", "int", 4,
         "Async iterator bounded-queue depth; re-read at iterator reset "
         "(epoch boundary), so the tuner can deepen prefetch live",
         lo=1, hi=64, mutability=LIVE)
_declare("DL4J_TPU_RETRACE_THRESHOLD", "int", 3,
         "Distinct trace signatures per jitted step before the retrace "
         "sentinel warns")
# --- pallas kernels --------------------------------------------------------
_declare("DL4J_TPU_PALLAS", "bool", False,
         "Global Pallas kernel family switch (tri-state: unset=auto)")
_declare("DL4J_TPU_PALLAS_XENT", "bool", False,
         "Fused softmax-cross-entropy Pallas kernel (tri-state)")
_declare("DL4J_TPU_PALLAS_LSTM", "bool", False,
         "LSTM cell Pallas helper mode (tri-state: forced/off/auto)")
_declare("DL4J_TPU_PALLAS_CONVBN", "bool", False,
         "Conv+BN folding Pallas helper mode (tri-state)")
# --- telemetry -------------------------------------------------------------
_declare("DL4J_TPU_TELEMETRY", "bool", False,
         "Master telemetry gate: tracer, health monitor, metrics "
         "observation, flight recorder (gate-off = zero allocation)")
_declare("DL4J_TPU_TELEMETRY_BUFFER", "int", 65536,
         "Chrome-trace ring buffer capacity (events)", lo=1)
_declare("DL4J_TPU_PROFILE_LAYERS", "int", 0,
         "Sample per-layer forward spans every N dispatches (0 = off)",
         lo=0)
_declare("DL4J_TPU_STALL_TIMEOUT", "float", 300.0,
         "Stall-watchdog heartbeat timeout (seconds)", lo=0.0)
_declare("DL4J_TPU_STRAGGLER_RATIO", "float", 2.0,
         "Worker wall-time ratio over the median that flags a straggler",
         lo=1.0)
_declare("DL4J_TPU_FLIGHT_DIR", "str", None,
         "Flight-recorder bundle directory (default: $TMPDIR)")
_declare("DL4J_TPU_FLIGHT_KEEP", "int", 20,
         "Flight bundles kept before rotation deletes the oldest", lo=1)
_declare("DL4J_TPU_COLLECTIVE_CENSUS", "bool", False,
         "Count collectives in compiled HLO after each windowed compile")
_declare("DL4J_TPU_PEAK_FLOPS", "float", 0.0,
         "Per-device peak FLOP/s override for MFU accounting (0 = "
         "detect)", lo=0.0)
_declare("DL4J_TPU_PEAK_TFLOPS", "float", 197.0,
         "Per-device peak TFLOP/s for the static roofline model", lo=0.0)
_declare("DL4J_TPU_HBM_GBPS", "float", 0.0,
         "HBM bandwidth override for roofline verdicts (0 = detect)",
         lo=0.0)
_declare("DL4J_TPU_ICI_GBPS", "float", 90.0,
         "ICI link bandwidth for the collective cost model", lo=0.0)
_declare("DL4J_TPU_DCN_GBPS", "float", 12.5,
         "DCN link bandwidth for the collective cost model", lo=0.0)
# --- tuner -----------------------------------------------------------------
_declare("DL4J_TPU_AUTOTUNE", "bool", False,
         "Closed-loop tuner gate: epoch/scrape ticks may adjust LIVE "
         "knobs; every decision journaled + reversible (docs/TUNING.md)")
_declare("DL4J_TPU_TUNER_DIR", "str", None,
         "Tuner decision-journal directory (default: $TMPDIR)")
# --- serving ---------------------------------------------------------------
_declare("DL4J_TPU_SERVING", "bool", False,
         "Serving runtime gate (admission metrics, breaker wiring)")
_declare("DL4J_TPU_SERVING_SHED", "str", "reject_newest",
         "Overload shed policy: reject_newest | reject_oldest")
_declare("DL4J_TPU_SERVING_DEADLINE", "float", 0.0,
         "Default per-request deadline seconds (0 = none)", lo=0.0)
_declare("DL4J_TPU_SERVING_BREAK_AFTER", "int", 5,
         "Consecutive dispatch failures that open the circuit breaker",
         lo=1)
_declare("DL4J_TPU_SERVING_COOLDOWN", "float", 1.0,
         "Open-breaker cooldown before half-open probes (seconds)",
         lo=0.0)
_declare("DL4J_TPU_SERVING_PROBES", "int", 2,
         "Half-open probe successes required to close the breaker", lo=1)
_declare("DL4J_TPU_WARM_CACHE", "str", None,
         "Warm-start manifest dir; also persists every compile into "
         "the compile-cache dir (serving/warmstart.py)")
# --- distributed / resilience ----------------------------------------------
_declare("DL4J_TPU_CHAOS", "str", None,
         "Fault-injection schedule, comma-separated point@N:M clauses "
         "(resilience/chaos.py)")
_declare("DL4J_TPU_HEARTBEAT_TIMEOUT", "float", 60.0,
         "Missed-heartbeat eviction timeout (seconds)", lo=0.0)
_declare("DL4J_TPU_EVICT_SKEW_RATIO", "float", 0.0,
         "Wall-time skew ratio that drains a straggling worker (0 = "
         "disabled)", lo=0.0)
_declare("DL4J_TPU_EVICT_SKEW_SPLITS", "int", 3,
         "Consecutive skewed splits before the drain trips", lo=1)
_declare("DL4J_TPU_REJOIN_BACKOFF", "float", 0.05,
         "Rejoin barrier retry backoff base (seconds)", lo=0.0)
_declare("DL4J_TPU_RETRY_ATTEMPTS", "int", 3,
         "Retried-IO attempt budget (resilience/retry.py)", lo=1)
_declare("DL4J_TPU_RETRY_BACKOFF", "float", 0.05,
         "Retried-IO backoff base (seconds)", lo=0.0)
_declare("DL4J_TPU_RETRY_JITTER", "float", 0.0,
         "Retried-IO decorrelated jitter fraction", lo=0.0)
_declare("DL4J_TPU_COORDINATOR_TIMEOUT", "float", 60.0,
         "Multi-process coordinator connect timeout (seconds)", lo=0.0)
_declare("DL4J_TPU_STREAM_TIMEOUT", "float", 5.0,
         "Streaming split fetch timeout (seconds)", lo=0.0)
_declare("DL4J_TPU_STREAM_GRACE", "float", 5.0,
         "Streaming shutdown drain grace (seconds)", lo=0.0)
_declare("DL4J_TPU_BLOB_TIMEOUT", "float", 300.0,
         "Cloud-storage blob transfer timeout (seconds)", lo=0.0)
# --- util / native ---------------------------------------------------------
_declare("DL4J_TPU_LOCKCHECK", "bool", False,
         "Lock-order sentinel on the tracked hot locks")
_declare("DL4J_TPU_LOCKCHECK_HOLD_S", "float", 1.0,
         "Held-too-long threshold for the lock sentinel (seconds)",
         lo=0.0)
_declare("DL4J_TPU_DATA_DIR", "str", None,
         "Dataset fetcher cache root (default ~/.deeplearning4j_tpu)")
_declare("DL4J_TPU_NATIVE_CACHE", "str", None,
         "Compiled native-ops artifact cache dir")
_declare("DL4J_TPU_DISABLE_NATIVE", "bool", False,
         "Force the pure-JAX fallbacks even when native ops built")


def knob(name: str) -> Optional[Knob]:
    """The declaration for `name`, or None for undeclared gates."""
    return KNOBS.get(name)


def set_override(name: str, raw: Any) -> str:
    """Install a tuner override for a declared LIVE knob. The value is
    type-coerced and range-clamped by the declaration, stored as its
    canonical string (every reader re-parses through the normal
    value()/int_value() path), and returned. Raises KeyError for
    undeclared knobs and ValueError for static ones — the tuner must
    never steer a gate whose readers cache at import time."""
    k = KNOBS.get(name)
    if k is None:
        raise KeyError(f"{name} is not a declared knob")
    if k.mutability != LIVE:
        raise ValueError(f"{name} is {k.mutability}, not live-tunable")
    coerced = k.coerce(raw)
    canonical = ("1" if coerced else "0") if k.kind == "bool" \
        else str(coerced)
    with _overrides_lock:
        _overrides[name] = canonical
    return canonical


def clear_override(name: str) -> None:
    """Drop one tuner override (revert to env/default). No-op when the
    override is absent."""
    with _overrides_lock:
        _overrides.pop(name, None)


def clear_overrides() -> None:
    """Drop ALL tuner overrides (tuner shutdown / test re-arm)."""
    with _overrides_lock:
        _overrides.clear()


def overrides() -> Dict[str, str]:
    """Snapshot of the active tuner overrides (name -> raw string)."""
    with _overrides_lock:
        return dict(_overrides)


def effective(name: str) -> Tuple[Optional[str], str]:
    """(raw value, provenance) for a gate: the tuner override when one is
    installed, else the environment, else the declared default (None for
    undeclared gates). Provenance is ``tuner | env | default``."""
    ov = _overrides.get(name)
    if ov is not None:
        return ov, PROV_TUNER
    env = os.environ.get(name)
    if env is not None:
        return env.strip(), PROV_ENV
    k = KNOBS.get(name)
    default = None if k is None or k.default is None else str(k.default)
    return default, PROV_DEFAULT


def describe() -> List[Dict[str, Any]]:
    """Registry rows for every declared knob plus any set-but-undeclared
    DL4J_TPU_* environment variables (flagged ``declared: False`` so
    `cli config` surfaces spelling drift instead of hiding it)."""
    rows: List[Dict[str, Any]] = []
    for name in sorted(KNOBS):
        k = KNOBS[name]
        val, prov = effective(name)
        rows.append({
            "name": name, "kind": k.kind, "default": k.default,
            "range": [k.lo, k.hi] if (k.lo is not None or
                                      k.hi is not None) else None,
            "mutability": k.mutability, "value": val,
            "provenance": prov, "help": k.help, "declared": True,
        })
    for name in sorted(os.environ):
        if name.startswith("DL4J_TPU_") and name not in KNOBS:
            rows.append({
                "name": name, "kind": "str", "default": None,
                "range": None, "mutability": STATIC,
                "value": os.environ[name].strip(),
                "provenance": PROV_ENV, "help": "",
                "declared": False,
            })
    return rows


def snapshot() -> Dict[str, Dict[str, str]]:
    """Compact effective-knob snapshot for flight bundles and profile
    reports: every knob that DIFFERS from its declared default (plus all
    active overrides), as name -> {value, provenance}. Small by
    construction — an all-defaults run snapshots empty."""
    out: Dict[str, Dict[str, str]] = {}
    for row in describe():
        default = (None if row["default"] is None
                   else ("1" if row["default"] is True
                         else "0" if row["default"] is False
                         else str(row["default"])))
        if row["provenance"] != PROV_DEFAULT and row["value"] != default:
            out[row["name"]] = {"value": row["value"],
                                "provenance": row["provenance"]}
    return out
