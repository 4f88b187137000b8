"""Host-level elasticity over the DCN axis — membership where a member
is a HOST.

PR 7's elastic membership treats every worker lane as an independent
member; on one host that is exactly right. Across hosts the failure
domain changes: when a process (= one `jax.process_index()`, one host in
the multi-controller job) dies, EVERY lane it owned dies with it, and the
postmortem wants one incident record for the host, not one per lane. This
module stretches the same generation-numbered registry across that
boundary (the large-scale-TF coordinator posture, PAPERS.md 1603.04467):

  * ``HostMembership`` is a MembershipRegistry holding BOTH tiers: the
    worker lanes the shard-queue masters (distributed/master.py) compete
    over, and one ``host{p}`` member per process that OWNS a contiguous
    block of lanes. The masters keep querying lanes; the host tier is
    bookkeeping they never see.
  * Host loss cascades: evicting ``host{p}`` evicts its lanes (reason
    propagated, per-lane flight bundles suppressed) and writes ONE
    host-level eviction bundle. The lanes' shards then requeue onto
    surviving hosts' lanes through the PR 7 shard-queue machinery
    untouched — the shard layout is cut by the CONFIGURED lane count, so
    the degraded aggregate stays bitwise-equal to the fault-free run
    (divisor fallback in SharedTrainingMaster covers ragged survivors).
  * Chaos fires at the DCN level: ``DL4J_TPU_CHAOS=host_loss@N`` with
    ``probe_host_loss()`` called once per split probes the active hosts
    in process order, so hit N names the Nth probed host slot — every
    process counts the same probes and converges on the same victim
    without exchanging a byte.
  * Silent hosts ride the same heartbeat state machine: a host that
    stops calling ``host_heartbeat`` goes suspect then evicted by the
    ordinary ``suspect_silent`` pass, scoped to the host tier.
  * Rejoin happens ONLY at the split-boundary checkpoint barrier: the
    base ``barrier()`` readmits the host (decorrelated backoff, resume
    split from the atomic manifest), and the override below re-registers
    its lanes in the same admission — a lane never rejoins ahead of its
    host.

The bottom half is the subprocess harness: spawn N real CPU
multi-controller processes over a loopback coordinator so the whole DCN
path is tier-1-testable without a chip. Real collectives cannot outlive a
truly dead peer inside one SPMD program, so the chaos arcs simulate host
death at the MEMBERSHIP level (the process keeps answering collectives;
its lanes and shards are gone) — the same convention the single-host
masters use for lane death, lifted one level.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

from deeplearning4j_tpu.distributed.membership import (
    MembershipRegistry,
    WorkerState,
)
from deeplearning4j_tpu.resilience import chaos

HOST_PREFIX = "host"


def host_key(process_index: int) -> str:
    """Registry member id for the host tier: ``host{p}``."""
    return f"{HOST_PREFIX}{int(process_index)}"


def parse_host_key(worker_id) -> Optional[int]:
    """Inverse of host_key; None for ordinary lane ids."""
    s = str(worker_id)
    if not s.startswith(HOST_PREFIX):
        return None
    try:
        return int(s[len(HOST_PREFIX):])
    except ValueError:
        return None


def lane_plan(n_lanes: int, n_hosts: int) -> Dict[int, List[int]]:
    """Contiguous lane blocks per host — the jax.devices() layout (a
    process's devices are contiguous), so host h's lanes are exactly the
    global-mesh rows its DCN slot covers."""
    if n_hosts <= 0 or n_lanes <= 0 or n_lanes % n_hosts:
        raise ValueError(
            f"{n_lanes} lanes do not split evenly over {n_hosts} hosts")
    per = n_lanes // n_hosts
    return {h: list(range(h * per, (h + 1) * per)) for h in range(n_hosts)}


class HostMembership(MembershipRegistry):
    """Two-tier elastic membership: worker lanes + the hosts that own
    them. Drop-in where the masters expect a MembershipRegistry — they
    only ever query lane ids."""

    def __init__(self, n_hosts: int, n_lanes: int, **kw):
        super().__init__(**kw)
        self.n_hosts = int(n_hosts)
        self.n_lanes = int(n_lanes)
        self._host_lanes = lane_plan(self.n_lanes, self.n_hosts)
        for p in range(self.n_hosts):
            self.register(host_key(p))
            for lane in self._host_lanes[p]:
                self.register(lane)

    # ------------------------------------------------------------------
    # topology views
    # ------------------------------------------------------------------
    def lanes_of(self, process_index: int) -> List[int]:
        return list(self._host_lanes.get(int(process_index), ()))

    def host_of(self, lane: int) -> int:
        return int(lane) // (self.n_lanes // self.n_hosts)

    def host_indices(self) -> List[int]:
        return list(range(self.n_hosts))

    def active_host_indices(self) -> List[int]:
        return [p for p in range(self.n_hosts)
                if self.is_active(host_key(p))]

    def surviving_lanes(self) -> List[int]:
        """Active lanes of active hosts, ascending — what the shard queue
        refits on after a host loss."""
        out = []
        for p in self.active_host_indices():
            out.extend(l for l in self._host_lanes[p] if self.is_active(l))
        return sorted(out)

    # ------------------------------------------------------------------
    # host lifecycle
    # ------------------------------------------------------------------
    def host_heartbeat(self, process_index: int) -> None:
        """One host-level liveness stamp (the per-split analogue of a
        lane's beat; each process beats for ITSELF, transitions travel
        through coordinate_membership)."""
        self.heartbeat(host_key(process_index))

    def evict(self, worker_id, reason: str, exc=None,
              flight: bool = True) -> bool:
        """Host evictions cascade to the host's lanes FIRST (per-lane
        bundles suppressed; the lanes' rejoin schedule is cleared so the
        barrier can never readmit a lane ahead of its host), then the
        host member itself is evicted — one generation-visible incident,
        one flight bundle."""
        p = parse_host_key(worker_id)
        if p is not None and p in self._host_lanes:
            for lane in self._host_lanes[p]:
                super().evict(lane, reason, exc=exc, flight=False)
                self._pin_lane(lane)
            return super().evict(worker_id, reason, exc=exc, flight=flight)
        return super().evict(worker_id, reason, exc=exc, flight=flight)

    def _pin_lane(self, lane) -> None:
        """A cascade-evicted lane rejoins only through its host."""
        with self._lock:
            info = self._workers.get(lane)
            if info is not None and info.state is WorkerState.EVICTED:
                info.rejoin_not_before = None

    def evict_host(self, process_index: int, reason: str,
                   exc=None) -> bool:
        return self.evict(host_key(process_index), reason, exc=exc)

    def report_host_failure(self, process_index: int,
                            exc: Optional[BaseException] = None) -> None:
        """Exception-detected host death (CoordinatorTimeoutError and
        torn-transport OSErrors read as host_loss — transient and
        rejoinable; anything else is an application error)."""
        self.report_failure(host_key(process_index), exc)

    def silent_hosts(self, now: Optional[float] = None) -> List[int]:
        """Missed-heartbeat pass scoped to the HOST tier: first silence
        marks the host suspect, continued silence evicts it (cascading to
        its lanes via the evict override). Returns newly-evicted process
        indices."""
        evicted = self.suspect_silent(
            now=now, only=[host_key(p) for p in range(self.n_hosts)])
        return [p for p in (parse_host_key(w) for w in evicted)
                if p is not None]

    def probe_host_loss(self) -> List[int]:
        """The DCN-level chaos probe, called once per split: probes active
        hosts in process order, one ``host_loss`` fault-point hit each, so
        ``DL4J_TPU_CHAOS=host_loss@N`` kills the Nth probed host slot.
        Counters advance identically on every process (same active set,
        same order), so all controllers agree on the victim without
        coordination. Returns the process indices evicted this probe."""
        victims: List[int] = []
        for p in sorted(self.active_host_indices()):
            try:
                chaos.fault_point("host_loss")
            except chaos.ChaosError as e:
                self.evict_host(p, "host_loss", exc=e)
                victims.append(p)
        return victims

    def barrier(self, splits_done: int, model=None,
                checkpoint_manager=None) -> List[Any]:
        """Split-boundary admission, host-aware: the base barrier
        readmits due hosts (and any independently-evicted lanes of LIVE
        hosts); every host admitted here gets its lanes re-registered in
        the same admission, resume split copied from the host's manifest
        agreement."""
        admitted = super().barrier(splits_done, model=model,
                                   checkpoint_manager=checkpoint_manager)
        for w in list(admitted):
            p = parse_host_key(w)
            if p is None or p not in self._host_lanes:
                continue
            host_info = self.get(w)
            for lane in self._host_lanes[p]:
                info = self.register(lane)
                if host_info is not None:
                    info.resume_split = host_info.resume_split
        return admitted


# ---------------------------------------------------------------------------
# the subprocess two-process harness (CPU, loopback coordinator)
# ---------------------------------------------------------------------------


def find_free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def loopback_env(rank: int, num_processes: int, port: int,
                 device_count: int = 2,
                 extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Environment for one spawned controller: forced-CPU virtual devices
    plus the declarative jax.distributed addressing runtime.initialize()
    reads. One process per chip is the rule — a parent that has touched
    JAX holds its accelerator, and a child that needs it fails or hangs —
    so every child this harness spawns is pinned to JAX_PLATFORMS=cpu."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORM_NAME", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={device_count}",
        "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
        "JAX_NUM_PROCESSES": str(num_processes),
        "JAX_PROCESS_ID": str(rank),
    })
    if extra:
        env.update(extra)
    return env


def spawn_local_cluster(worker_script: str, num_processes: int = 2,
                        device_count: int = 2, timeout: float = 300.0,
                        extra_env: Optional[Dict[str, str]] = None,
                        per_rank_env: Optional[
                            Sequence[Optional[Dict[str, str]]]] = None,
                        args: Sequence[str] = ()
                        ) -> List[Tuple[int, str, str]]:
    """Spawn ``num_processes`` real CPU multi-controller processes running
    ``worker_script`` over a loopback coordinator and wait for all of
    them. Returns per-rank ``(returncode, stdout, stderr)``; a rank that
    timed out reports returncode -9 with a synthetic stderr note (and the
    whole cluster is killed — a hung collective must not hang the test).

    ``per_rank_env`` overlays rank-specific vars (e.g. chaos on one host
    only) on top of ``extra_env``."""
    port = find_free_port()
    procs = []
    for rank in range(num_processes):
        extra = dict(extra_env or {})
        if per_rank_env is not None and per_rank_env[rank]:
            extra.update(per_rank_env[rank])
        env = loopback_env(rank, num_processes, port,
                           device_count=device_count, extra=extra)
        procs.append(subprocess.Popen(
            [sys.executable, worker_script, *args], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    results: List[Tuple[int, str, str]] = []
    timed_out = False
    for p in procs:
        try:
            out, err = p.communicate(timeout=timeout)
            results.append((p.returncode, out, err))
        except subprocess.TimeoutExpired:
            timed_out = True
            for q in procs:
                q.kill()
            out, err = p.communicate()
            results.append((-9, out or "",
                            (err or "") + "\n[harness] rank timed out"))
    if timed_out:
        # drain any ranks queued after the timeout with a short grace
        for i, p in enumerate(procs):
            if i >= len(results):
                try:
                    out, err = p.communicate(timeout=5)
                    results.append((p.returncode, out, err))
                except subprocess.TimeoutExpired:
                    p.kill()
                    results.append((-9, "", "[harness] rank timed out"))
    return results


# failure signatures that mean the ENVIRONMENT forbids subprocess
# multi-controller (sandboxed CI without loopback listeners, ancient
# jaxlib distributed service) rather than a bug in the code under test
_ENV_LIMIT_MARKERS = (
    "deadline_exceeded", "unavailable", "failed to connect",
    "connection refused", "coordinator", "barrier timed out",
    "timed out", "permission denied", "unimplemented",
    "distributed service", "grpc",
    # old-jaxlib CPU host emulation: the coordination service forms but
    # device collectives can't lower — the same limit that fails the
    # pre-existing dist_worker SPMD epoch in this environment
    "multiprocess computations aren't implemented",
)


def collectives_supported() -> bool:
    """Whether this backend can run cross-process DEVICE collectives
    (old-jaxlib CPU host emulation forms the coordination service but
    cannot lower multiprocess computations). Callers fall back to
    coordination-service-only exchanges when False."""
    import jax

    if jax.process_count() == 1:
        return True
    try:
        import jax.numpy as jnp
        from jax.experimental import multihost_utils

        multihost_utils.process_allgather(jnp.zeros((), jnp.float32))
        return True
    except Exception:
        return False


def cluster_env_limit(results: Sequence[Tuple[int, str, str]]
                      ) -> Optional[str]:
    """None when every rank exited 0; a skip-label string when the
    failure pattern-matches an environment limit (the tp x sp bench-cell
    convention: skip-with-a-label, never silently pass); raises nothing —
    a genuine assertion failure in a worker returns None-like falsy by
    NOT matching, so callers still fail loudly on real bugs."""
    if all(rc == 0 for rc, _, _ in results):
        return None
    for rc, out, err in results:
        if rc == 0:
            continue
        blob = f"{out}\n{err}".lower()
        for marker in _ENV_LIMIT_MARKERS:
            if marker in blob:
                return (f"env forbids subprocess multi-controller "
                        f"({marker}; rc={rc})")
    return None
