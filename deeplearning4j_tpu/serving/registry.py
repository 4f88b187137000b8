"""ModelRegistry — many named, versioned models behind one fleet.

PR 8 hardened ONE model's serving path; the registry grows that into a
fleet (ROADMAP item 2, TF-Serving's version-manager shape from
PAPERS.md; DL4J ParallelInference's multi-model layer from PAPER.md):
every ``(name, version)`` gets its OWN ``InferenceServer`` — its own
buckets, breaker, deadline policy, queue — so one model's overload or
open breaker never sheds a neighbor's traffic.

Sources served side by side with no user-code changes:

  * a live model object (anything with a jitted ``output(x)``) or a raw
    ``dispatch(batch)`` callable,
  * a zoo config by name (``zoo:LeNet`` — built and initialized here),
  * a ``modelimport`` Keras HDF5 file (``*.h5`` / ``*.keras``),
  * a native checkpoint zip (``models/serialization.py``),
  * a CheckpointManager checkpoint DIRECTORY (the continuous-learning
    publish target, distributed/continuous.py): the ``latest.json``
    pointer (or newest step) is resolved through its manifest and the
    zip's sha256 is verified BEFORE a dispatchable is built — a torn
    publish is rejected with IOError, never served.

Warm starts: when a warm-cache dir is configured (``DL4J_TPU_WARM_CACHE``
or the ``warm_cache_dir`` argument) the registry enables the JAX
persistent compilation cache (serving/warmstart.py; the executables live
in the one directory util/compile_cache.py owns, the manifests under the
warm-cache dir) and ``warm()``
both dispatches every bucket AND records the warm manifest — so the
NEXT replica's ``warm()`` needs no example at all: it synthesizes the
batch from the manifest and its "compiles" are disk reads
(``watcher().cold_compile_count()`` stays flat, tier-1 asserted).

Canary plumbing: each version's dispatch is wrapped with the
``canary_dispatch`` / ``canary_nan`` chaos fault points
(resilience/chaos.py) which are ARMED ONLY while that version is the
active canary (``ModelVersion.canary``) — a deliberately-broken canary
is injectable with ``DL4J_TPU_CHAOS=canary_dispatch@1:2:3`` while the
stable version and all warmups stay untouched. Traffic splitting and
the SLO-gated ramp live in serving/router.py.
"""
from __future__ import annotations

import os
import threading
import weakref
from typing import Callable, Dict, List, Optional

import numpy as np

from deeplearning4j_tpu.resilience import chaos
from deeplearning4j_tpu.serving import buckets as buckets_mod
from deeplearning4j_tpu.serving import warmstart
from deeplearning4j_tpu.serving.breaker import CircuitBreaker
from deeplearning4j_tpu.serving.runtime import InferenceServer
from deeplearning4j_tpu.util import compile_cache

ZOO_PREFIX = "zoo:"

# live registries for /models (weak: a dropped registry must not pin
# itself — the _SERVERS pattern from serving/runtime.py)
_REGISTRIES: "weakref.WeakSet[ModelRegistry]" = weakref.WeakSet()


def live_registries() -> List["ModelRegistry"]:
    return list(_REGISTRIES)


def resolve_model(source):
    """Turn a registration source into a live model object — the "no
    user-code changes" contract: the same string a user would hand the
    import/restore CLIs works here verbatim.

      ``zoo:<Name>``       a zoo architecture, built + initialized
      ``*.h5`` ``*.keras`` a Keras file through modelimport
      ``*.zip``            a native serialized model
      a directory          a CheckpointManager publish dir — resolved
                           via its latest-pointer/manifest with the
                           sha256 verified first (torn publish raises)
      anything else        returned as-is (already a model object)
    """
    if not isinstance(source, str):
        return source
    if os.path.isdir(source):
        from deeplearning4j_tpu.distributed.continuous import (
            load_published_model,
        )

        model, _manifest = load_published_model(source)
        return model
    if source.startswith(ZOO_PREFIX):
        from deeplearning4j_tpu import zoo

        name = source[len(ZOO_PREFIX):]
        builder = getattr(zoo, name, None)
        if builder is None:
            raise ValueError(f"unknown zoo model {name!r}")
        model = builder().init()
        return model
    if source.endswith((".h5", ".hdf5", ".keras")):
        from deeplearning4j_tpu.modelimport.keras import (
            import_keras_model_and_weights,
        )

        return import_keras_model_and_weights(source)
    if source.endswith(".zip"):
        from deeplearning4j_tpu.models.serialization import restore_model

        return restore_model(source, load_updater=False)
    raise ValueError(
        f"model source {source!r} is not zoo:<Name>, *.h5/*.keras, "
        f"*.zip, or a checkpoint directory")


class ModelVersion:
    """One served version: a name + version tag bound to its own
    InferenceServer. ``canary`` is flipped by the router for the
    duration of a rollout — it arms the canary chaos points and routes
    this version's outcomes into the per-version SLO selectors."""

    def __init__(self, name: str, version: str, server: InferenceServer):
        self.name = name
        self.version = version
        self.server = server
        self.canary = False
        # the UNWRAPPED dispatch + serving policy this version was
        # registered with: what Autoscaler.for_model clones replica
        # servers from (replicas serve stable traffic, so they never
        # carry the canary fault wrapper)
        self.dispatch: Optional[Callable] = None
        self.server_kwargs: Dict[str, object] = {}

    @property
    def key(self) -> str:
        return f"{self.name}:{self.version}"

    def snapshot(self) -> dict:
        snap = self.server.snapshot()
        snap.update(model=self.name, version=self.version,
                    canary=self.canary)
        return snap


class ModelEntry:
    """All versions of one named model + which one is stable."""

    def __init__(self, name: str):
        self.name = name
        self.versions: Dict[str, ModelVersion] = {}
        self.stable: Optional[str] = None

    def stable_version(self) -> ModelVersion:
        if self.stable is None:
            raise KeyError(f"model {self.name!r} has no stable version")
        return self.versions[self.stable]


class ModelRegistry:
    """The fleet's model table. Thread-safe; servers are constructed at
    register() time (their dispatcher threads idle until traffic) and
    drained at unregister()/shutdown()."""

    def __init__(self, mesh=None, warm_cache_dir: Optional[str] = None):
        self.mesh = mesh
        self._lock = threading.Lock()
        # the version chain (ModelEntry.versions / .stable) is mutated
        # ONLY inside this registry's locked methods — callers holding a
        # ModelEntry from entry() must treat it as read-only
        self._entries: Dict[str, ModelEntry] = {}  # guarded-by: self._lock
        compile_cache.ensure()
        d = warm_cache_dir or warmstart.cache_dir_from_env()
        self.warm_cache_dir = warmstart.enable(d) if d else None
        _REGISTRIES.add(self)

    # ------------------------------------------------------------------
    # registration
    # ------------------------------------------------------------------
    def register(self, name: str, source=None,
                 dispatch: Optional[Callable] = None,
                 version: str = "v1",
                 stable: Optional[bool] = None,
                 **server_kwargs) -> ModelVersion:
        """Add one ``(name, version)``. ``source`` is anything
        ``resolve_model`` accepts; ``dispatch`` bypasses model loading
        (tests, custom stacks). Per-model serving policy — buckets,
        breaker, deadline, shed policy, queue/batch limits — rides in
        through ``server_kwargs`` untouched. The first version of a name
        becomes stable unless ``stable=False``."""
        if source is None and dispatch is None:
            raise ValueError("register() needs a model source or a "
                             "dispatch callable")
        model = resolve_model(source) if source is not None else None
        server_kwargs.setdefault("name", f"{name}:{version}")
        mv_holder: List[ModelVersion] = []
        if dispatch is None:
            inner, align = InferenceServer._build_model_dispatch(
                model, self.mesh)
            server_kwargs.setdefault(
                "buckets", buckets_mod.BucketSpec(
                    int(server_kwargs.get("batch_limit", 32)), align=align))
        else:
            inner = dispatch
        server = InferenceServer(
            dispatch=self._canary_faulted(inner, mv_holder),
            mesh=self.mesh, **server_kwargs)
        server.model = model
        mv = ModelVersion(name, version, server)
        mv.dispatch = inner
        mv.server_kwargs = {k: v for k, v in server_kwargs.items()
                            if k not in ("name", "warmup_example")}
        mv_holder.append(mv)
        with self._lock:
            entry = self._entries.setdefault(name, ModelEntry(name))
            if version in entry.versions:
                raise ValueError(f"{mv.key} already registered")
            entry.versions[version] = mv
            if stable or (stable is None and entry.stable is None):
                entry.stable = version
        return mv

    @staticmethod
    def _canary_faulted(inner: Callable, mv_holder: List[ModelVersion]):
        """Wrap a dispatch with the canary chaos points, armed only
        while this version IS the canary — warmups and stable traffic
        never consume the injection schedule, so
        ``DL4J_TPU_CHAOS=canary_dispatch@1:2:3`` breaks exactly the
        first three canary batches."""

        def dispatch(xp):
            mv = mv_holder[0] if mv_holder else None
            is_canary = mv is not None and mv.canary
            if is_canary:
                chaos.fault_point("canary_dispatch")
            out = inner(xp)
            if is_canary and chaos.silent_fault("canary_nan"):
                out = np.full_like(
                    np.asarray(out, dtype=np.float32), np.nan)
            return out

        return dispatch

    # ------------------------------------------------------------------
    # warm starts
    # ------------------------------------------------------------------
    def warm(self, name: str, version: Optional[str] = None,
             example=None) -> ModelVersion:
        """Warm one version's buckets. With an ``example`` (first boot):
        dispatch every bucket and, when a warm cache is configured,
        record the manifest. Without one (replica restart): synthesize
        the example from the recorded manifest — the warmup then runs
        entirely against the persistent compilation cache and performs
        zero cold compiles."""
        mv = self.get(name, version)
        if example is None:
            if self.warm_cache_dir is None:
                raise ValueError(
                    f"warm({mv.key}) without an example needs a warm "
                    f"cache dir (DL4J_TPU_WARM_CACHE) with a recorded "
                    f"manifest")
            manifest = warmstart.load_manifest(
                self.warm_cache_dir, name, mv.version)
            if manifest is None:
                raise FileNotFoundError(
                    f"no warm manifest for {mv.key} under "
                    f"{self.warm_cache_dir} — first boot must pass an "
                    f"example")
            example = warmstart.warmup_example(manifest)
        mv.server.warmup(example)
        if self.warm_cache_dir is not None:
            warmstart.record_warm(self.warm_cache_dir, name, mv.version,
                                  example, mv.server.buckets.sizes)
        return mv

    def replica_example(self, mv: "ModelVersion"):
        """The warm-manifest example a NEW replica of ``mv`` warms up
        with (serving/autoscaler.py scale-out boots through this, so
        its compiles are persistent-cache reads — zero cold compiles);
        None when no warm cache / manifest is recorded."""
        if self.warm_cache_dir is None:
            return None
        manifest = warmstart.load_manifest(self.warm_cache_dir, mv.name,
                                           mv.version)
        if manifest is None:
            return None
        return warmstart.warmup_example(manifest)

    # ------------------------------------------------------------------
    # lookup / lifecycle
    # ------------------------------------------------------------------
    def get(self, name: str, version: Optional[str] = None) -> ModelVersion:
        with self._lock:
            entry = self._entries.get(name)
            if entry is None:
                raise KeyError(f"model {name!r} not registered")
            if version is None:
                return entry.stable_version()
            mv = entry.versions.get(version)
            if mv is None:
                raise KeyError(f"model {name}:{version} not registered")
            return mv

    def entry(self, name: str) -> ModelEntry:
        with self._lock:
            entry = self._entries.get(name)
            if entry is None:
                raise KeyError(f"model {name!r} not registered")
            return entry

    def models(self) -> List[str]:
        with self._lock:
            return sorted(self._entries)

    def set_stable(self, name: str, version: str) -> None:
        with self._lock:
            entry = self._entries[name]
            if version not in entry.versions:
                raise KeyError(f"model {name}:{version} not registered")
            entry.stable = version

    def unregister(self, name: str, version: Optional[str] = None,
                   timeout: float = 5.0) -> None:
        """Drain and drop one version (or the whole model)."""
        with self._lock:
            entry = self._entries.get(name)
            if entry is None:
                return
            if version is None:
                victims = list(entry.versions.values())
                del self._entries[name]
            else:
                mv = entry.versions.pop(version, None)
                victims = [mv] if mv is not None else []
                if entry.stable == version:
                    entry.stable = next(iter(entry.versions), None)
                if not entry.versions:
                    del self._entries[name]
        for mv in victims:
            mv.server.shutdown(timeout=timeout)

    def shutdown(self, timeout: float = 5.0) -> None:
        with self._lock:
            victims = [mv for e in self._entries.values()
                       for mv in e.versions.values()]
            self._entries.clear()
        for mv in victims:
            mv.server.shutdown(timeout=timeout)

    def snapshot(self) -> dict:
        """Machine-readable fleet state for /models and `serve rollout`."""
        with self._lock:
            entries = {name: (e.stable, list(e.versions.values()))
                       for name, e in self._entries.items()}
        return {
            "warm_cache_dir": self.warm_cache_dir,
            "models": {
                name: {
                    "stable": stable,
                    "versions": [mv.snapshot() for mv in mvs],
                }
                for name, (stable, mvs) in sorted(entries.items())
            },
        }
