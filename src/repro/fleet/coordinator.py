"""FleetCoordinator — N StreamRuntime replicas behind one front door.

The scaling story (ROADMAP north star): PR 1 proved the per-chunk body of a
StreamRuntime is contract-equivalent to one-shot ``figmn.fit``, so the unit
of data-parallel scale-out is the *replica*: one runtime per data shard,
each with its own lifecycle budget, drift detector and checkpoint lineage.
This module adds the four things N replicas need to act as ONE model:

  routing        — ShardRouter splits every incoming batch into per-replica
                   sub-streams (hash ring / round-robin / feature-affinity),
  consolidation  — every ``consolidate_every`` ingest rounds (a lifecycle
                   boundary: replicas have just run their final lifecycle
                   pass, so pools are pruned and within budget) the replica
                   mixtures merge into one global mixture
                   (fleet.consolidate, star or gossip topology) with
                   ``sum(sp)`` conserved exactly,
  serving        — the consolidated mixture is *published* to a read-only
                   ScoringFrontend; ``score``/``score_async`` read the
                   snapshot and never touch (or wait on) ingesting
                   replicas,
  autoscaling    — when ``FleetConfig.autoscale`` is set, an Autoscaler
                   (fleet/autoscale.py) reads the telemetry deltas at each
                   consolidation boundary and the coordinator executes its
                   decisions: scale-up splits the hottest replica's pool by
                   responsibility-weighted bisection into a fresh runtime
                   (slots move bit-identically — sum(sp) conserved
                   EXACTLY); scale-down drains the coldest replica into a
                   peer via consolidate.drain (moment-matched merging,
                   never truncation).  Each event bumps the replica-set
                   ``epoch``.

Replicas carry stable integer *ids* (``replica_ids``): positions in
``self.replicas`` shift when a replica is removed, ids never do — they key
checkpoint directories, the router's hash ring and the autoscaler's delta
baselines, so everything stays stable across scale events and restarts.

Checkpointing writes one fleet manifest + per-replica payloads (each via
its own CheckpointManager, so replica saves stay independently atomic and
resumable).  The manifest pins the replica-id set, the epoch and each
replica's step, so ``resume`` after any number of scale events rebuilds
exactly that membership and restores a whole cut; it then re-consolidates
to rebuild the serving snapshot.

Placement (ISSUE 10): the coordinator is deliberately ignorant of WHERE a
replica runs.  ``FleetConfig(placement="thread")`` (default) builds
in-process StreamRuntimes; ``placement="process"`` builds
RemoteReplicaHandles (fleet/remote.py) — each replica is a worker process
behind repro.rpc, and every coordinator/supervisor/autoscaler code path
below drives it through the same duck-typed surface.  The autoscaler
therefore allocates and releases worker PROCESSES at consolidation
boundaries; unsupervised process fleets ingest their shards on parallel
threads (real multi-process parallelism — the N-process scaling curve in
benchmarks/figmn_multihost.py), while supervised delivery keeps the
watchdog's sequential semantics.

Checkpoint directories are INCARNATION-namespaced
(``<root>/replica_<rid>/inc_<n>``): every time a coordinator creates a
replica fresh (construction, scale-up), it allocates a new incarnation —
so a restarted fleet whose replica ids collide with an earlier run can
never resume another life's ``replica_<rid>`` steps (the supervisor's
restore ceiling reads an empty dir, not a stale one).  A supervisor
respawn of a dead worker process deliberately KEEPS the incarnation: the
respawned process must restore its own checkpoints.  The fleet manifest
pins incarnations; legacy manifests map to the bare un-namespaced dirs.
"""
from __future__ import annotations

import dataclasses
import json
import logging
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.types import Array, FIGMNConfig, FIGMNState
from repro.fleet import autoscale as autoscale_mod
from repro.fleet.autoscale import (Autoscaler, AutoscaleConfig,
                                   ReplicaSignal, ScaleDecision,
                                   ServingSignal)
from repro.fleet.consolidate import consolidate as _consolidate
from repro.fleet.consolidate import drain as _drain
from repro.fleet.consolidate import sp_mass
from repro.fleet.router import RouterConfig, ShardRouter
from repro.fleet.scoring import AdmissionConfig, ScoringFrontend
from repro.fleet.telemetry import (ConsolidationEvent, FleetTelemetry,
                                   ScaleEvent)
from repro.ft.retry import RetryPolicy
from repro.ft.straggler import StragglerConfig, StragglerMonitor
from repro.ft.supervisor import FleetSupervisor, SupervisorConfig
from repro.obs import registry as obs_registry
from repro.obs.trace import span
from repro.rpc.client import RpcConfig
from repro.stream import RuntimeConfig, StreamRuntime, costmodel

_log = logging.getLogger(__name__)

_MANIFEST = "fleet_manifest.json"


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Fleet-level knobs (per-replica knobs live in RuntimeConfig).

    n_replicas:        INITIAL StreamRuntime replicas (= data shards);
                       with autoscaling the live count moves within
                       [autoscale.min_replicas, autoscale.max_replicas].
    router:            "round_robin" | "hash" | "affinity".
    topology:          consolidation topology, "star" | "gossip".
    consolidate_every: ingest rounds between consolidations (0 ⇒ never
                       automatic — only an explicit consolidate() call, or
                       the implicit one on the first score of an
                       unpublished fleet).
    global_kmax:       slot budget of the consolidated mixture (0 ⇒ the
                       replica cfg.kmax).
    autoscale:         None ⇒ fixed membership; an AutoscaleConfig enables
                       telemetry-driven scale events at consolidation
                       boundaries.
    checkpoint_dir:    fleet manifest + per-replica checkpoint root.
    score_workers:     ScoringFrontend worker threads.
    admission:         None ⇒ every async read is its own device dispatch;
                       an AdmissionConfig micro-batches compatible queued
                       reads (same kind/targets/return_var) into one
                       dispatch under its max-delay + max-batch policy.
    factor_cache_size: LRU capacity of the serving eq. 27 factor cache
                       (entries are (snapshot version, targets) bundles;
                       <= 0 disables caching — bit-identical either way).
    supervisor:        None ⇒ unsupervised delivery (a replica exception
                       propagates to the caller, the pre-FT behaviour); a
                       SupervisorConfig enables the watchdog + escalating
                       recovery ladder of ft/supervisor.py — chunk retries
                       on the replicas, quarantine + shard re-routing on
                       crash/hang, checkpoint-restore rejoin at
                       consolidation boundaries, exact mass accounting.
    max_staleness_s:   serving freshness bound during degraded operation
                       (None = unbounded): reads against a snapshot older
                       than this raise StalenessExceeded instead of
                       silently answering from the distant past.
    serve_retry:       budgeted backoff+jitter resubmission of async reads
                       bounced by admission control (None = bounce to the
                       caller with the retry-after hint).
    straggler:         divergence thresholds of the per-replica chunk-
                       latency monitor (None = StragglerConfig defaults);
                       with supervisor.straggler_drain the monitor's
                       evictions become mass-conserving drains.
    placement:         where replicas live: "thread" (in-process
                       StreamRuntimes, the default) | "process" (one
                       worker process per replica behind repro.rpc —
                       fleet/remote.py handles wearing the same replica
                       protocol).
    rpc:               wire/process knobs for placement="process" (None =
                       RpcConfig defaults; an unset ingest_silence_s is
                       resolved from the supervisor's heartbeat timeout
                       so the watchdog always quarantines before the
                       wire kills a silent worker).  Process placement
                       runs on the CPU only: a parent that has started
                       an accelerator backend holds the chip, and the
                       workers it spawns could not reach it.
    """
    n_replicas: int = 2
    router: str = "round_robin"
    topology: str = "star"
    consolidate_every: int = 1
    global_kmax: int = 0
    autoscale: Optional[AutoscaleConfig] = None
    checkpoint_dir: Optional[str] = None
    score_workers: int = 2
    router_seed: int = 0
    admission: Optional[AdmissionConfig] = None
    factor_cache_size: int = 16
    supervisor: Optional[SupervisorConfig] = None
    max_staleness_s: Optional[float] = None
    serve_retry: Optional[RetryPolicy] = None
    straggler: Optional[StragglerConfig] = None
    placement: str = "thread"
    rpc: Optional[RpcConfig] = None


class FleetCoordinator:
    """Owns the replicas, the router, the merge clock and the snapshot."""

    def __init__(self, cfg: FIGMNConfig, fcfg: FleetConfig = FleetConfig(),
                 rcfg: RuntimeConfig = RuntimeConfig(),
                 registry: Optional[obs_registry.Registry] = None):
        self.cfg = cfg
        self.fcfg = fcfg
        self.rcfg = rcfg
        self._registry = registry or obs_registry.default_registry()
        if fcfg.placement not in ("thread", "process"):
            raise ValueError(f"placement must be 'thread' or 'process', "
                             f"got {fcfg.placement!r}")
        self._remote = fcfg.placement == "process"
        if self._remote and jax.default_backend() != "cpu":
            raise RuntimeError(
                f"placement='process' needs the CPU backend, but this "
                f"process runs on {jax.default_backend()!r}: a chip "
                f"belongs to the one process that opened it, so worker "
                f"processes cannot share it.  Use placement='thread'.")
        self._rpc = self._resolve_rpc()
        self.router = ShardRouter(
            RouterConfig(policy=fcfg.router, seed=fcfg.router_seed),
            fcfg.n_replicas)
        self.replica_ids: List[int] = list(range(fcfg.n_replicas))
        self._next_id = fcfg.n_replicas
        #: rid -> checkpoint-dir incarnation (None = legacy bare dir).
        #: Allocated fresh for every replica THIS coordinator creates, so
        #: an id recycled across fleet runs never sees old steps.
        self._incarnations: Dict[int, Optional[int]] = {}
        for rid in self.replica_ids:
            self._incarnations[rid] = self._alloc_incarnation(rid)
        self.replicas: List[object] = [
            self._make_replica(rid) for rid in self.replica_ids]
        # serving mirrors the replicas' RESOLVED ingest path: a forced
        # dense RuntimeConfig.path must score densely too, or the fleet's
        # two read fronts (replica.score vs coordinator.score) would
        # disagree — the sparse score is a strict lower bound.  The
        # resolution is the same table-first/heuristic-fallback decision
        # the replicas make (costmodel.decide is the non-recording twin:
        # each replica already counted its own resolution).
        resolved = costmodel.decide(
            cfg, requested=rcfg.path, chunk=rcfg.chunk,
            vmem_budget=rcfg.vmem_budget, device=rcfg.device,
            cost_table=rcfg.cost_table).path
        self.scoring = ScoringFrontend(
            cfg, workers=fcfg.score_workers,
            shortlist_c=cfg.shortlist_c if resolved == "sparse" else 0,
            registry=self._registry,
            cost_table=rcfg.cost_table, device=rcfg.device,
            admission=fcfg.admission,
            factor_cache_size=fcfg.factor_cache_size,
            max_staleness_s=fcfg.max_staleness_s,
            retry=fcfg.serve_retry)
        self.supervisor = (FleetSupervisor(fcfg.supervisor,
                                           registry=self._registry)
                           if fcfg.supervisor is not None else None)
        if self.supervisor is not None:
            for rid, r in zip(self.replica_ids, self.replicas):
                self.supervisor.attach(rid, r)
        self.telemetry = FleetTelemetry()
        self.autoscaler = (Autoscaler(fcfg.autoscale)
                           if fcfg.autoscale is not None else None)
        self.rounds = 0
        self.epoch = 0          # replica-set epoch (bumps on scale events)
        reg = self._registry
        self._m_consol_s = reg.histogram(
            "figmn_consolidation_seconds",
            "wall time of one fleet consolidation + publish")
        self._m_replicas = reg.gauge(
            "figmn_fleet_replicas", "live replica count")
        self._m_replicas.set(len(self.replicas))
        self._m_scale = {
            action: reg.counter("figmn_fleet_scale_events_total",
                                "autoscaler-executed membership changes",
                                {"action": action})
            for action in ("up", "down")}
        self._m_stragglers = reg.gauge(
            "figmn_fleet_stragglers",
            "replicas whose per-chunk ingest latency diverges from the "
            "fleet median (detection only)")
        # straggler detection (ft/straggler.py, detection-only): fed the
        # per-replica mean chunk latency of each consolidation window
        self.straggler = StragglerMonitor(
            [self._host(rid) for rid in self.replica_ids],
            fcfg.straggler or StragglerConfig())
        self._strag_last: Dict[int, Tuple[int, float]] = {}
        # serving-window clock: ServingSignal.window_s spans consecutive
        # autoscale decisions
        self._serve_window_t = time.monotonic()

    @staticmethod
    def _host(rid: int) -> str:
        return f"replica_{rid}"

    @property
    def n_replicas(self) -> int:
        """Live membership size (≠ fcfg.n_replicas after scale events)."""
        return len(self.replicas)

    @property
    def _ckpt_root(self) -> Optional[str]:
        """Fleet checkpoint root: FleetConfig wins, else a RuntimeConfig
        checkpoint_dir is promoted to fleet root — replicas must NEVER
        share one literal directory (same chunk_idx steps would rmtree
        each other's saves and resume() would silently swap states)."""
        return self.fcfg.checkpoint_dir or self.rcfg.checkpoint_dir

    def _resolve_rpc(self) -> Optional[RpcConfig]:
        """Concrete RpcConfig for process placement (None for threads).
        An unset ingest_silence_s resolves to 2x the supervisor heartbeat
        timeout — the watchdog must always win the race and quarantine on
        heartbeat silence BEFORE the wire declares the worker hung and
        kills it (the kill then resolves the pending future)."""
        if not getattr(self, "_remote", False):
            return None
        rpc = self.fcfg.rpc or RpcConfig()
        if rpc.ingest_silence_s is None:
            if self.fcfg.supervisor is not None:
                hb = self.fcfg.supervisor.heartbeat_timeout_s
                silence = max(2.0 * hb, hb + 1.0)
            else:
                silence = 600.0
            rpc = dataclasses.replace(rpc, ingest_silence_s=silence)
        return rpc

    def _alloc_incarnation(self, rid: int) -> int:
        """Next unused incarnation number for this replica id's
        checkpoint dir: max of the existing ``inc_<n>`` subdirs + 1, so a
        freshly created replica always starts from an EMPTY directory —
        never another run's steps (legacy bare ``step_*`` dirs under
        ``replica_<rid>`` are likewise shadowed, not resumed)."""
        root = self._ckpt_root
        if root is None:
            return 0
        base = os.path.join(root, f"replica_{rid}")
        if not os.path.isdir(base):
            return 0
        incs = [int(name[4:]) for name in os.listdir(base)
                if name.startswith("inc_") and name[4:].isdigit()]
        return max(incs, default=-1) + 1

    def _replica_dir(self, rid: int) -> Optional[str]:
        root = self._ckpt_root
        if root is None:
            return None
        base = os.path.join(root, f"replica_{rid}")
        inc = self._incarnations.get(rid)
        return base if inc is None else os.path.join(base, f"inc_{inc}")

    def _rcfg_for_id(self, rid: int) -> RuntimeConfig:
        """Per-replica RuntimeConfig, checkpoint dir keyed by STABLE id +
        incarnation — positions shift on scale-down, directories must
        not, and recycled ids across fleet runs must not share steps.  A
        supervised fleet also installs its SupervisorConfig.retry as the
        replicas' chunk-retry policy (rung 1 of the ladder) unless the
        RuntimeConfig already carries its own."""
        out = self.rcfg
        d = self._replica_dir(rid)
        if d is not None:
            out = dataclasses.replace(out, checkpoint_dir=d)
        if self.fcfg.supervisor is not None and out.chunk_retry is None:
            out = dataclasses.replace(
                out, chunk_retry=self.fcfg.supervisor.retry)
        return out

    def _make_replica(self, rid: int):
        """Construct one replica at the configured placement.  For
        process placement this SPAWNS a worker (and blocks on its init
        handshake) — callers only create replicas at construction, scale
        events and resume, all consolidation-boundary operations."""
        rcfg = self._rcfg_for_id(rid)
        if self._remote:
            from repro.fleet.remote import RemoteReplicaHandle
            return RemoteReplicaHandle(rid, self.cfg, rcfg, self._rpc)
        return StreamRuntime(self.cfg, rcfg, registry=self._registry)

    @staticmethod
    def _close_replica(replica) -> None:
        close = getattr(replica, "close", None)
        if callable(close):
            close()

    # ------------------------------------------------------------------
    # ingestion
    # ------------------------------------------------------------------

    def ingest(self, xs) -> Dict[str, object]:
        """Route one (N, D) batch to the replicas; returns fleet summary.

        One call is one fleet "round": every replica ingests its shard
        (running its own chunking/lifecycle/drift), then — at the cadence
        of ``consolidate_every`` — the round ends at a lifecycle boundary
        with a consolidation + snapshot publish, followed by at most one
        autoscale decision/event (scale events only ever happen at these
        boundaries: pools are pruned, budget-merged and just published).
        """
        xs = np.asarray(xs, np.float32)
        if self.supervisor is None:
            # unsupervised: exceptions propagate to the caller unchanged.
            # Process placement ingests shards on parallel threads — each
            # thread only blocks on its worker's socket, so N processes
            # genuinely compute concurrently (the scaling curve).  Thread
            # placement stays sequential: the runtimes share one device.
            shards = self.router.route(xs)
            work = [(r, xs[idx]) for r, idx in zip(self.replicas, shards)
                    if idx.size]
            if self._remote and len(work) > 1:
                errs: List[BaseException] = []

                def _run(replica, shard):
                    try:
                        replica.ingest(shard)
                    except BaseException as e:  # noqa: BLE001 re-raised
                        errs.append(e)

                threads = [threading.Thread(target=_run, args=w,
                                            daemon=True) for w in work]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                if errs:
                    raise errs[0]
            else:
                for replica, shard in work:
                    replica.ingest(shard)
        else:
            self._deliver(xs)
        self.rounds += 1
        every = self.fcfg.consolidate_every
        if every > 0 and self.rounds % every == 0:
            self.consolidate()
            if self.autoscaler is not None:
                self._maybe_autoscale()
        return self.summary()

    def _deliver(self, xs: np.ndarray, depth: int = 0) -> None:
        """Supervised delivery with re-routing.

        Each shard runs under the supervisor's watchdog; a failed shard's
        replica is quarantined (and masked out of the router) and the
        shard re-routes through the surviving membership — recursively,
        because the re-routed delivery can itself hit a sick replica.
        ``depth`` caps the cascade at SupervisorConfig.reroute_attempts:
        past it (correlated fleet-wide failure) the points are accounted
        as lost rather than looping forever.  Router counts stay exact:
        a failed delivery is un-counted before its points route again.
        """
        sup = self.supervisor
        for pos, idx in enumerate(self.router.route(xs)):
            if not idx.size:
                continue
            rid = self.replica_ids[pos]
            if rid in sup.quarantined:
                # only reachable when the LAST live replica went down
                # (the router refuses to mask it): nowhere to re-route
                self.router.uncount(pos, idx.size)
                sup.record_dropped(self, idx.size,
                                   "all replicas quarantined")
                continue
            if sup.ingest_shard(self, rid, self.replicas[pos], xs[idx]):
                continue
            self.router.uncount(pos, idx.size)
            if depth >= sup.cfg.reroute_attempts:
                sup.record_dropped(
                    self, idx.size,
                    f"re-route budget exhausted at depth {depth}")
                continue
            self._deliver(xs[idx], depth + 1)

    def install_faults(self, injector) -> None:
        """Attach a ft.faults.FaultInjector's plan to the live replicas
        (chunk hooks on the real runtimes — chaos runs exercise the real
        retry/quarantine/restore paths, never mocks).  Remote replicas
        receive the plan over RPC and arm it on the runtime inside their
        worker process (fault hooks need on_chunk_start, which only
        exists where the rows are)."""
        for rid, r in zip(self.replica_ids, self.replicas):
            if hasattr(r, "install_faults"):
                r.install_faults(injector)
            else:
                injector.attach(rid, r)

    # ------------------------------------------------------------------
    # consolidation / serving
    # ------------------------------------------------------------------

    def consolidate(self) -> FIGMNState:
        """Merge all replica mixtures; publish the result for serving.

        A consolidation boundary is also the supervisor's recovery
        boundary: quarantined replicas restore + rejoin FIRST (so a
        recovered replica's state is part of this merge), and replicas
        still quarantined are EXCLUDED from the merge — their state is
        suspect (a hung ingest thread may still be mutating it), and the
        serving contract during recovery is the last GOOD mixture, not a
        half-poisoned one."""
        if self.supervisor is not None:
            self.supervisor.tick(self)
        t0 = time.perf_counter()
        with span("fleet.consolidate", topology=self.fcfg.topology,
                  replicas=len(self.replicas)) as sp:
            if self.supervisor is not None and self.supervisor.quarantined:
                states = [r.state for rid, r
                          in zip(self.replica_ids, self.replicas)
                          if rid not in self.supervisor.quarantined]
                if not states:
                    # whole fleet down: keep serving the last snapshot
                    return self.global_state
            else:
                states = [r.state for r in self.replicas]
            active_in = sum(int(s.n_active) for s in states)
            global_state, merges = _consolidate(
                self.cfg, states, topology=self.fcfg.topology,
                kmax_out=self.fcfg.global_kmax)
            version = self.scoring.publish(global_state)
            sp.set(version=version, merges=merges,
                   active_out=int(global_state.n_active))
        wall = time.perf_counter() - t0
        self.telemetry.record_consolidation(ConsolidationEvent(
            round_idx=self.rounds, version=version,
            topology=self.fcfg.topology, n_states_in=len(states),
            active_in=active_in, active_out=int(global_state.n_active),
            merges=merges,
            sp_mass=sp_mass(global_state),
            wall_s=wall))
        self._m_consol_s.observe(wall)
        self._update_stragglers()
        if self.supervisor is not None:
            self.supervisor.escalate_stragglers(self)
        return global_state

    def _update_stragglers(self) -> None:
        """Feed the detection-only straggler monitor the mean per-chunk
        ingest latency each replica paid since the last consolidation, and
        surface the suspect count (gauge + log line).  Replicas that
        ingested nothing this window report nothing — an idle replica is
        cold, not slow."""
        for rid, r in zip(self.replica_ids, self.replicas):
            chunks = int(r.telemetry.total_chunks)
            wall = float(r.telemetry.total_time_s)
            base_c, base_w = self._strag_last.get(rid, (0, 0.0))
            self._strag_last[rid] = (chunks, wall)
            dc, dw = chunks - base_c, wall - base_w
            if dc > 0 and dw > 0:
                self.straggler.report(self._host(rid), dw / dc)
        suspects = self.straggler.suspects()
        self._m_stragglers.set(len(suspects))
        if suspects:
            _log.warning(
                "fleet straggler(s) detected (per-chunk latency > "
                "%.1fx fleet median): %s",
                self.straggler.cfg.slow_factor, ", ".join(suspects))

    @property
    def global_state(self) -> Optional[FIGMNState]:
        """The last consolidated mixture (None before first consolidate)."""
        state, _ = self.scoring.snapshot()
        return state

    def score(self, xs) -> Array:
        """Serving read: (N,) log-densities under the published snapshot
        (consolidates first if nothing was published yet)."""
        if not self.scoring.ready:
            self.consolidate()
        return self.scoring.score(xs)

    def score_async(self, xs):
        """Non-blocking serving read; returns a Future of score(xs)."""
        if not self.scoring.ready:
            self.consolidate()
        return self.scoring.score_async(xs)

    def predict(self, xs, targets, return_var: bool = False):
        """Serving conditional read (eq. 27): (N, o) reconstructions of
        ``targets`` under the published snapshot (consolidates first if
        nothing was published yet) — same snapshot contract as score.
        return_var=True returns a (mean, var) pair (conditional
        variance off the same cached factors)."""
        if not self.scoring.ready:
            self.consolidate()
        return self.scoring.predict(xs, targets, return_var=return_var)

    def predict_async(self, xs, targets, return_var: bool = False):
        """Non-blocking conditional read; Future of predict(xs, targets).
        With FleetConfig.admission set, compatible queued reads coalesce
        into one device dispatch."""
        if not self.scoring.ready:
            self.consolidate()
        return self.scoring.predict_async(xs, targets,
                                          return_var=return_var)

    # ------------------------------------------------------------------
    # autoscaling
    # ------------------------------------------------------------------

    def _signals(self) -> List[ReplicaSignal]:
        counts = self.router.counts()
        budget = (self.rcfg.lifecycle.k_budget or self.cfg.kmax) \
            if self.rcfg.lifecycle is not None else self.cfg.kmax
        out = []
        for pos, (rid, r) in enumerate(zip(self.replica_ids,
                                           self.replicas)):
            if (self.supervisor is not None
                    and rid in self.supervisor.quarantined):
                continue        # frozen counters would read as cold
            s = r.telemetry.summary()
            out.append(ReplicaSignal(
                rid=rid, routed=counts[pos], chunks=int(s["chunks"]),
                drift_alarms=int(s["drift_alarms"]),
                active_k=int(r.state.n_active), budget=budget))
        return out

    def _serving_signal(self) -> ServingSignal:
        """Cumulative serving-side state for the autoscaler: total
        completed requests + the latency histogram's bucket counts, plus
        the wall seconds since the previous decision (the policy diffs the
        cumulative parts itself)."""
        now = time.monotonic()
        window = now - self._serve_window_t
        self._serve_window_t = now
        return ServingSignal.from_histogram(
            self.scoring.latency.snapshot(),
            self.scoring.requests_total, window)

    def _maybe_autoscale(self) -> Optional[ScaleDecision]:
        recovering = (self.supervisor is not None
                      and self.supervisor.recovering)
        decision = self.autoscaler.observe(self._signals(),
                                           self._serving_signal(),
                                           recovering=recovering)
        if decision.action == "up":
            self.scale_up(decision.rid, reason=decision.reason)
        elif decision.action == "down":
            self.scale_down(decision.rid, decision.peer,
                            reason=decision.reason)
        if decision.action != "hold":
            # membership (and, on down, the folded router counts) changed:
            # re-anchor the delta baseline so the next decision judges only
            # traffic that arrives AFTER the event
            self.autoscaler.rebaseline(self._signals())
        return decision

    def scale_up(self, rid: int, reason: str = "") -> bool:
        """Split replica ``rid``'s pool into itself + a fresh replica.

        Mass-conserving by construction: ``autoscale.split_state`` moves
        slots bit-identically, so the fleet's active-sp multiset is
        unchanged.  Returns False (no event) when the pool has fewer than
        two live components.
        """
        t0 = time.perf_counter()
        pos = self.replica_ids.index(rid)
        parent = self.replicas[pos]
        split = autoscale_mod.split_state(self.cfg, parent.export_pool())
        if split is None:
            return False
        kept, child_state, centroid = split
        mass_before = sp_mass(parent.state)
        new_id = self._next_id
        self._next_id += 1
        # a fresh replica is a fresh life: new incarnation dir (and, at
        # process placement, a newly allocated worker process)
        self._incarnations[new_id] = self._alloc_incarnation(new_id)
        child = self._make_replica(new_id)
        parent.import_pool(kept)
        child.import_pool(child_state)
        self.router.grow(new_id, centroid=centroid)
        self.replicas.append(child)
        self.replica_ids.append(new_id)
        self.epoch += 1
        self.straggler.add_host(self._host(new_id))
        if self.supervisor is not None:
            self.supervisor.attach(new_id, child)
            self.supervisor.delivered[new_id] = int(
                child.telemetry.total_points)
        self._m_scale["up"].inc()
        self._m_replicas.set(len(self.replicas))
        self.telemetry.record_scale(ScaleEvent(
            round_idx=self.rounds, epoch=self.epoch, action="up",
            rid=rid, peer=new_id, n_replicas=len(self.replicas),
            active_moved=int(child_state.n_active),
            sp_mass_before=mass_before,
            sp_mass_after=sp_mass(kept) + sp_mass(child_state),
            merges=0, reason=reason, wall_s=time.perf_counter() - t0))
        return True

    def scale_down(self, rid: int, peer_rid: int, reason: str = "") -> bool:
        """Drain replica ``rid`` into ``peer_rid`` and retire it.

        The drained pool is absorbed through ``consolidate.drain`` (union +
        moment-matched budget merging — never truncation); the pending
        spawn buffer moves too, so gate-failing points observed by the
        retired replica still get their lifecycle chance.
        """
        if rid == peer_rid:
            raise ValueError("cannot drain a replica into itself")
        t0 = time.perf_counter()
        pos = self.replica_ids.index(rid)
        peer_pos = self.replica_ids.index(peer_rid)
        cold, peer = self.replicas[pos], self.replicas[peer_pos]
        mass_before = sp_mass(cold.state) + sp_mass(peer.state)
        moved = int(cold.state.n_active)
        merged_state, merges = _drain(self.cfg, peer.export_pool(),
                                      cold.export_pool())
        peer.import_pool(merged_state)
        if len(cold.buffer):
            peer.buffer.push(cold.buffer.drain())
        self.router.shrink(pos, into=peer_pos)
        # the retiring replica's counter totals (ingested, quarantined,
        # ...) must keep counting toward the fleet aggregate or the
        # fleet-level mass identity breaks on every drain
        self.telemetry.absorb_retired(cold.telemetry.summary())
        del self.replicas[pos]
        del self.replica_ids[pos]
        self._incarnations.pop(rid, None)
        # at process placement a retired replica is a released worker
        self._close_replica(cold)
        self.epoch += 1
        self.straggler.remove_host(self._host(rid))
        self._strag_last.pop(rid, None)
        if self.supervisor is not None:
            # the peer's delivered baseline must absorb the drained
            # replica's points or the next rejoin accounting would read
            # the fold as replay; forget clears the retired id
            self.supervisor.forget(rid)
            self.supervisor.delivered[peer_rid] = int(
                peer.telemetry.total_points)
        self._m_scale["down"].inc()
        self._m_replicas.set(len(self.replicas))
        self.telemetry.record_scale(ScaleEvent(
            round_idx=self.rounds, epoch=self.epoch, action="down",
            rid=rid, peer=peer_rid, n_replicas=len(self.replicas),
            active_moved=moved, sp_mass_before=mass_before,
            sp_mass_after=sp_mass(merged_state), merges=merges,
            reason=reason, wall_s=time.perf_counter() - t0))
        return True

    # ------------------------------------------------------------------
    # telemetry / checkpointing
    # ------------------------------------------------------------------

    def summary(self) -> Dict[str, object]:
        s = self.telemetry.summary(
            [r.telemetry.summary() for r in self.replicas],
            self.router.load())
        s["epoch"] = self.epoch
        s["replica_ids"] = list(self.replica_ids)
        s["stragglers"] = self.straggler.suspects()
        if self.supervisor is not None:
            s["quarantined_replicas"] = sorted(self.supervisor.quarantined)
            s["supervisor_points_lost"] = self.supervisor.points_lost
            s["supervisor_points_replayed"] = \
                self.supervisor.points_replayed
        s["serving_degraded"] = self.scoring.degraded
        return s

    def checkpoint(self) -> None:
        """One manifest + N independently-atomic replica payloads."""
        d = self._ckpt_root
        if d is None:
            raise RuntimeError("no checkpoint_dir configured")
        for rid, r in zip(self.replica_ids, self.replicas):
            if (self.supervisor is not None
                    and rid in self.supervisor.quarantined):
                # suspect state must never overwrite the last good save
                continue
            r.checkpoint()
        # Pin the exact replica-id set, epoch and per-replica steps this
        # manifest describes: replicas also auto-checkpoint on every
        # ingest, so "latest" may be newer than the manifest after a
        # crash — resume restores THESE ids at THESE steps so the fleet
        # always comes back as one consistent cut, even across scale
        # events (a retired replica's directory stays on disk but is no
        # longer referenced).
        manifest = {"n_replicas": len(self.replicas),
                    "replica_ids": list(self.replica_ids),
                    "incarnations": {str(rid): self._incarnations.get(rid)
                                     for rid in self.replica_ids},
                    "epoch": self.epoch,
                    "next_replica_id": self._next_id,
                    "rounds": self.rounds,
                    "topology": self.fcfg.topology,
                    "snapshot_version": self.scoring.version,
                    "replica_steps": [r.ckpt.latest_step()
                                      for r in self.replicas],
                    "router": self.router.export_state(),
                    "autoscale": (self.autoscaler.export_state()
                                  if self.autoscaler is not None
                                  else None),
                    "supervisor": (self.supervisor.export_state()
                                   if self.supervisor is not None
                                   else None)}
        tmp = os.path.join(d, _MANIFEST + ".tmp")
        with open(tmp, "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(d, _MANIFEST))

    def resume(self) -> bool:
        """Restore manifest + every replica (incl. drift/telemetry state);
        re-consolidate to rebuild the serving snapshot.  True if resumed.

        Scale events change membership, so resume rebuilds the EXACT
        replica-id set the manifest pins (whole-cut semantics): a fleet
        configured with n_replicas=1 that autoscaled to 3 before the
        checkpoint comes back with those same 3 replicas, states
        bit-identical.
        """
        d = self._ckpt_root
        if d is None:
            raise RuntimeError("no checkpoint_dir configured")
        path = os.path.join(d, _MANIFEST)
        if not os.path.exists(path):
            return False
        with open(path) as f:
            manifest = json.load(f)
        ids = manifest.get("replica_ids")
        if ids is None:
            # legacy (pre-autoscale) manifest: identity membership only
            if manifest["n_replicas"] != len(self.replicas):
                raise ValueError(
                    f"manifest has {manifest['n_replicas']} replicas, "
                    f"fleet configured with {len(self.replicas)}")
            ids = list(self.replica_ids)
        ids = [int(i) for i in ids]
        incs = manifest.get("incarnations")
        if incs is None:
            # legacy manifest (pre-incarnation): bare replica_<rid> dirs
            pinned: Dict[int, Optional[int]] = {rid: None for rid in ids}
        else:
            pinned = {int(k): (None if v is None else int(v))
                      for k, v in incs.items()}
            pinned = {rid: pinned.get(rid) for rid in ids}
        rebuild = (ids != self.replica_ids
                   or any(pinned[rid] != self._incarnations.get(rid)
                          for rid in ids))
        if rebuild:
            # replicas must be rebuilt on the manifest's PINNED
            # incarnation dirs — a fresh coordinator allocated new (empty)
            # ones at construction, which is exactly what stops it from
            # reading this manifest's steps by accident
            old_incarnations = dict(self._incarnations)
            self._incarnations = dict(pinned)
            replicas = [self._make_replica(rid) for rid in ids]
        else:
            replicas = self.replicas
        steps = manifest.get("replica_steps", [None] * len(ids))
        # Resolve and validate the WHOLE cut before touching any replica:
        # a partial restore (some replicas rolled back, some not) is worse
        # than failing.  None (legacy manifest) resolves to that replica's
        # latest step; a replica with no checkpoint at all ⇒ clean False.
        # A PINNED step can only be missing when replica auto-checkpoint
        # GC (keep_n) outran fleet.checkpoint() — that is an operator
        # error (checkpoint the fleet at least every keep_n-1 ingest
        # rounds), and it is loud, not a silent False.
        def _abort() -> None:
            # a failed resume must leave the fleet exactly as it was:
            # release any just-built replicas (worker processes!) and
            # roll the incarnation map back to this run's allocations
            if rebuild:
                for r in replicas:
                    self._close_replica(r)
                self._incarnations = old_incarnations

        resolved = [step if step is not None else r.ckpt.latest_step()
                    for r, step in zip(replicas, steps)]
        if None in resolved:
            _abort()
            return False
        lost = [i for i, (r, step) in enumerate(zip(replicas, resolved))
                if step not in r.ckpt.all_steps()]
        if lost:
            if any(s is not None for s in steps):
                _abort()
                raise RuntimeError(
                    f"fleet manifest pins replica steps {steps} but "
                    f"replicas {lost} no longer have theirs (GC'd by "
                    f"keep_n); call fleet.checkpoint() at least every "
                    f"keep_n-1 ingest rounds or raise "
                    f"RuntimeConfig.keep_n")
            _abort()
            return False
        for r, step in zip(replicas, resolved):
            if not r.resume(step=step):
                _abort()
                return False
        if rebuild:
            for r in self.replicas:
                self._close_replica(r)       # release the replaced set
            self.replicas = replicas
            self.replica_ids = list(ids)
            self.router = ShardRouter(
                RouterConfig(policy=self.fcfg.router,
                             seed=self.fcfg.router_seed), len(ids))
            self.straggler = StragglerMonitor(
                [self._host(rid) for rid in ids], self.straggler.cfg)
            self._strag_last = {}
            self._m_replicas.set(len(self.replicas))
        self.rounds = int(manifest["rounds"])
        self.epoch = int(manifest.get("epoch", 0))
        self._next_id = int(manifest.get("next_replica_id", len(ids)))
        self.router.load_state(manifest["router"])
        if self.autoscaler is not None \
                and manifest.get("autoscale") is not None:
            self.autoscaler.load_state(manifest["autoscale"])
        if self.supervisor is not None:
            if manifest.get("supervisor") is not None:
                self.supervisor.load_state(manifest["supervisor"])
            # the restored counters ARE the delivered truth of this cut
            self.supervisor.sync_delivered(self.replica_ids, self.replicas)
            for rid, r in zip(self.replica_ids, self.replicas):
                self.supervisor.attach(rid, r)
        if int(manifest.get("snapshot_version", 0)) > 0:
            t0 = time.perf_counter()
            state, merges = _consolidate(
                self.cfg, [r.state for r in self.replicas],
                topology=self.fcfg.topology,
                kmax_out=self.fcfg.global_kmax)
            version = self.scoring.publish(
                state, version=manifest["snapshot_version"])
            # log the republish so summary() (snapshot_version, global K,
            # mass) reflects the serving snapshot immediately, not only
            # after the next scheduled consolidation
            self.telemetry.record_consolidation(ConsolidationEvent(
                round_idx=self.rounds, version=version,
                topology=self.fcfg.topology,
                n_states_in=len(self.replicas),
                active_in=sum(int(r.state.n_active)
                              for r in self.replicas),
                active_out=int(state.n_active), merges=merges,
                sp_mass=sp_mass(state),
                wall_s=time.perf_counter() - t0))
        return True

    # ------------------------------------------------------------------
    # fleet-wide observability (per-worker registry aggregation)
    # ------------------------------------------------------------------

    def worker_metric_sources(self) -> List[object]:
        """Scrape callables for every replica that keeps its own obs
        registry (process placement) — feed these to
        ``obs.export.serve_metrics(extra_sources=...)`` so ONE /metrics
        endpoint serves the merged fleet view.  Thread replicas record
        into the coordinator's registry already and contribute nothing
        here."""
        return [r.metrics_dump for r in self.replicas
                if callable(getattr(r, "metrics_dump", None))]

    def fleet_metrics(self) -> Dict[str, object]:
        """One merged registry dump: the coordinator's own registry +
        every live worker's scraped dump (mergeable-histogram reduce).
        A dead or quarantined worker is skipped for this scrape — the
        aggregate must stay serveable through partial failure."""
        from repro.obs import export as obs_export
        dumps = [obs_export.registry_dump(self._registry)]
        for src in self.worker_metric_sources():
            try:
                dumps.append(src())
            except Exception:
                continue
        return obs_export.merge_dumps(dumps)

    def close(self, cancel_pending: bool = False) -> None:
        self.scoring.close(cancel_pending)
        for r in self.replicas:
            self._close_replica(r)
