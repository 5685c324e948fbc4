"""StreamRuntime — the orchestrator that owns the full online loop.

One object unifies what previously lived in four places (core.figmn one-shot
fits, kernels.figmn_stream segments, ft.anomaly ad-hoc loops, example
scripts): chunked ingestion (ingest.py), pool lifecycle (lifecycle.py),
drift handling (drift.py) and telemetry (telemetry.py), with
checkpoint-backed resume via checkpoint.manager.

Invariant (tested): with lifecycle and drift disabled, ``ingest`` over any
chunking equals ONE ``core.figmn.fit`` pass over the concatenated stream —
chunking only re-slices the lax.scan, it never changes the math.  This is
the contract that lets later scaling PRs (sharded replicas via core.merge,
async serving) swap the per-chunk body without re-validating the learner.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

import jax

from repro.checkpoint import CheckpointManager
from repro.core import figmn, inference, shortlist
from repro.core.types import Array, FIGMNConfig, FIGMNState, chi2_quantile
from repro.obs import registry as obs_registry
from repro.obs.trace import span
from repro.stream import costmodel
from repro.stream import drift as drift_mod
from repro.stream import ingest, lifecycle, telemetry
from repro.ft.anomaly import AnomalyDetector
from repro.ft.retry import RetryPolicy


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Orchestration knobs (the FIGMN hyper-parameters live in FIGMNConfig).

    chunk:            micro-batch size (points per dispatch).
    path:             "auto" | "scan" | "vmem" | "sparse" (see
                      ingest.select_path; "sparse" — the top-C shortlist
                      body — needs cfg.shortlist_c > 0 and is what "auto"
                      picks whenever the config enables a shortlist).
    lifecycle:        pool-management policy; None disables (creation and
                      §2.3 pruning then happen inline in the scan body,
                      matching one-shot figmn.fit exactly).
    drift:            drift policy; None disables detection entirely.
    checkpoint_dir:   enables checkpoint/resume; None disables.
    checkpoint_every: chunks between periodic saves (0 ⇒ only final/fork).
    vmem_budget:      bytes assumed available for the VMEM-resident
                      kernel; None (the default) takes the device kind's
                      entry in ``repro.devices``
                      (costmodel.resolve_vmem_budget).
    device:           explicit backend platform ("cpu"/"tpu") the
                      dispatch decision is for; None keys off the process
                      default backend.  A checkpoint restored on
                      different hardware re-resolves against the new
                      device instead of replaying a stale decision.
    cost_table:       a costmodel.CostTable (or a path to its JSON dump)
                      of measured per-path costs; when present and it has
                      cells for this device key, dispatch picks the
                      measured-fastest path instead of the heuristic.
                      None ⇒ the PR-6 heuristic, bit-compatibly.
    telemetry_anomaly: learn a FIGMN over the runtime's own telemetry
                      (ft.anomaly) and flag anomalous chunks.
    on_nonfinite:     NaN/Inf row policy, applied by ``ingest.finite_guard``
                      before any chunk can touch Λ: "drop" quarantines the
                      bad rows (default — state bit-identical to a stream
                      that never contained them), "reject" quarantines the
                      whole chunk, "raise" raises NonFiniteChunkError.
                      Quarantined rows land in the
                      figmn_points_quarantined_total counter and the
                      telemetry's ``quarantined`` total.
    chunk_retry:      recovery-ladder rung 1 (ft.retry.RetryPolicy): a
                      chunk whose ingest raises is retried with backoff +
                      seeded jitter.  Safe because the chunk body is
                      atomic — ``self.state`` is only reassigned after the
                      jitted body returns, so a failed attempt leaves the
                      chunk cleanly un-applied.  None disables (errors
                      surface immediately); the fleet supervisor installs
                      its policy on replicas it supervises.
    """
    chunk: int = 256
    path: str = "auto"
    lifecycle: Optional[lifecycle.LifecycleConfig] = None
    drift: Optional[drift_mod.DriftConfig] = None
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0
    keep_n: int = 3
    vmem_budget: Optional[int] = None
    device: Optional[str] = None
    cost_table: Optional[object] = None
    telemetry_anomaly: bool = False
    telemetry_capacity: int = 4096
    on_nonfinite: str = "drop"
    chunk_retry: Optional[RetryPolicy] = None


class StreamRuntime:
    """Owns mixture state + ingestion loop for one unbounded stream."""

    def __init__(self, cfg: FIGMNConfig,
                 rcfg: RuntimeConfig = RuntimeConfig(),
                 registry: Optional[obs_registry.Registry] = None):
        self.cfg = cfg
        self.rcfg = rcfg
        # obs metrics are process-level by design: N replicas through one
        # registry aggregate into ONE ingest histogram/counter set (what a
        # scrape wants); callers needing isolation pass their own Registry
        reg = registry or obs_registry.default_registry()
        self._m_chunk_s = reg.histogram(
            "figmn_ingest_chunk_seconds",
            "per-chunk ingest wall time (device compute fenced)")
        self._m_points = reg.counter(
            "figmn_ingest_points_total", "points ingested")
        self._m_active = reg.gauge(
            "figmn_active_components", "live mixture components")
        self._m_drift = reg.counter(
            "figmn_drift_alarms_total", "drift detector alarms")
        self._m_lifecycle_s = reg.histogram(
            "figmn_lifecycle_pass_seconds",
            "off-hot-path pool maintenance wall time")
        self._m_pred_s = reg.gauge(
            "figmn_dispatch_predicted_seconds",
            "cost-table expected seconds for one chunk on the chosen path")
        self._m_meas_s = reg.gauge(
            "figmn_dispatch_measured_seconds",
            "last observed per-chunk ingest seconds (pair with "
            "figmn_dispatch_predicted_seconds)")
        self._m_quarantined = reg.counter(
            "figmn_points_quarantined_total",
            "NaN/Inf rows quarantined by the finite guard before they "
            "could touch the mixture")
        self._m_chunk_retries = reg.counter(
            "figmn_chunk_retries_total",
            "chunk ingest attempts retried (recovery-ladder rung 1)")
        # Chunk hooks (fault injection, supervisor heartbeats): objects
        # with optional ``on_chunk_start(chunk_idx, xc_host) ->
        # Optional[replacement_rows]`` (runs BEFORE the finite guard and
        # the ingest body; may raise — the failure enters the chunk-retry
        # ladder) and ``on_chunk_end(chunk_idx, n_points, latency_s)``
        # (observation only, fires after the chunk applied — the
        # supervisor's heartbeat stamp).
        self.chunk_hooks: List[object] = []
        self.state: FIGMNState = figmn.init_state(cfg)
        self.chunk_idx = 0
        # Pool epoch: bumped on EVERY state mutation (chunk ingest,
        # lifecycle pass, drift response, pool import, resume) — the
        # invalidation key for the eq. 27 factor cache.  A read that
        # captures (state, state_epoch) together can safely reuse cached
        # factors for that epoch; any mutation moves new reads to a fresh
        # cache line.
        self.state_epoch = 0
        self.factor_cache = inference.FactorCache(registry=reg)
        # Table-first, heuristic-fallback dispatch (stream.costmodel):
        # bit-compatible with ingest.select_path when rcfg.cost_table is
        # None.  The decision object keeps the expected per-point seconds
        # around for the predicted-vs-measured gauge pair.
        self.dispatch = costmodel.resolve_path(
            cfg, requested=rcfg.path, chunk=rcfg.chunk,
            vmem_budget=rcfg.vmem_budget, device=rcfg.device,
            cost_table=rcfg.cost_table, registry=reg)
        self.path = self.dispatch.path
        self.buffer = lifecycle.FailureBuffer(
            rcfg.lifecycle.buffer_cap if rcfg.lifecycle else 0, cfg.dim)
        self.detector = (drift_mod.DriftDetector(rcfg.drift)
                         if rcfg.drift else None)
        self.telemetry = telemetry.Telemetry(
            capacity=rcfg.telemetry_capacity,
            anomaly=AnomalyDetector(dim=3, warmup=16)
            if rcfg.telemetry_anomaly else None)
        self.ckpt = (CheckpointManager(rcfg.checkpoint_dir,
                                       keep_n=rcfg.keep_n)
                     if rcfg.checkpoint_dir else None)
        self._thresh = jnp.asarray(
            [float(chi2_quantile(cfg.dim, 1.0 - cfg.beta))], jnp.float32)
        # Deferred device→host syncs (see _ingest_chunk): the vmem accept
        # counter stays a device scalar between lifecycle boundaries, and
        # gate-failure masks wait device-side until the spawn pass needs
        # their host rows.
        self._accepted_dev = jnp.zeros((), jnp.int32)
        self._pending_fails = []

    # ------------------------------------------------------------------
    # ingestion
    # ------------------------------------------------------------------

    def ingest(self, xs) -> Dict[str, object]:
        """Feed an (N, D) stream segment; returns the telemetry summary.

        Callable repeatedly — state, telemetry, drift baselines and the
        lifecycle clock all carry across calls (an unbounded stream is just
        many ``ingest`` calls).
        """
        rc = self.rcfg
        with span("stream.ingest", n=int(np.shape(xs)[0]), path=self.path):
            loader = ingest.DoubleBufferedLoader(xs, rc.chunk,
                                                 self.cfg.dtype)
            for xc_dev, xc_host in loader:
                with span("stream.ingest_chunk", path=self.path,
                          n=int(xc_dev.shape[0])):
                    self._ingest_chunk_guarded(xc_dev, xc_host)
            if rc.lifecycle is not None:
                self._run_lifecycle(final=True)
            self._fold_accept_counter()
            if self.ckpt is not None:
                self.checkpoint()
        return self.telemetry.summary()

    def _ingest_chunk_guarded(self, xc_dev: Array,
                              xc_host: np.ndarray) -> None:
        """One chunk through hooks → finite guard → ingest body, under
        the chunk-retry policy (recovery-ladder rung 1).

        Retry is EXACT because the chunk body is atomic: ``_ingest_chunk``
        only reassigns ``self.state`` after the jitted body returns, and
        the hooks/guard run before any mutation — so a failed attempt
        leaves the chunk un-applied and a retry replays it from scratch
        (hooks fire again: a sticky injected fault keeps firing until it
        disarms or the budget escalates the error to the supervisor).
        ``NonFiniteChunkError`` is a policy decision, not a transient
        fault — it surfaces immediately.
        """
        policy = self.rcfg.chunk_retry
        delays = (policy.delays(salt=self.chunk_idx)
                  if policy is not None else iter(()))
        while True:
            try:
                self._ingest_chunk_once(xc_dev, xc_host)
                return
            except ingest.NonFiniteChunkError:
                raise
            except Exception:
                d = next(delays, None)
                if d is None:
                    raise
                self._m_chunk_retries.inc()
                time.sleep(d)

    def _ingest_chunk_once(self, xc_dev: Array,
                           xc_host: np.ndarray) -> None:
        idx = self.chunk_idx
        xh, replaced = xc_host, False
        with span("stream.chunk_guard"):
            for h in self.chunk_hooks:
                fn = getattr(h, "on_chunk_start", None)
                if fn is not None:
                    rep = fn(idx, xh)
                    if rep is not None:
                        xh, replaced = np.asarray(rep, np.float32), True
            xh, n_bad = ingest.finite_guard(xh, self.rcfg.on_nonfinite)
            if n_bad:
                self.telemetry.add_quarantined(n_bad)
                self._m_quarantined.inc(n_bad)
                replaced = True
            t0 = time.perf_counter()
            n_in = int(xh.shape[0])
            # the all-finite, un-replaced fast path reuses the device copy
            # already in flight — the guard costs one host isfinite sweep
            xd = (jax.device_put(jnp.asarray(xh, self.cfg.dtype))
                  if n_in and replaced else xc_dev)
        if n_in:
            self._ingest_chunk(xd, xh)
        for h in self.chunk_hooks:
            fn = getattr(h, "on_chunk_end", None)
            if fn is not None:
                # fires for fully-quarantined chunks too (n_in == 0): a
                # replica that is dropping poison is alive, not hung
                fn(idx, n_in, time.perf_counter() - t0)

    def _ingest_chunk(self, xc: Array, xc_host: np.ndarray) -> None:
        rc, cfg = self.rcfg, self.cfg
        # Host-side per-chunk consumers (drift CUSUM, ft.anomaly) genuinely
        # need floats every chunk; everything else (vmem accept counter,
        # gate-failure rows for the spawn buffer) stays device-side until a
        # lifecycle boundary — a per-chunk int()/float() pull would block
        # the host on the device and serialise the double-buffered feed.
        need_stats = self.detector is not None or rc.telemetry_anomaly
        t0 = time.perf_counter()
        with span("stream.chunk_pull"):
            n_created0 = int(self.state.n_created)
            formed = bool(jnp.any(self.state.active))
        path = self.path
        if path == "vmem" and not formed:
            path = "scan"            # kernel cannot create the first slot
        need_fails = path == "vmem" and rc.lifecycle is not None

        # Prequential stats: the chunk is scored against the PRE-update
        # mixture ("does the incoming data match what we learned so far").
        # Post-update stats are useless for drift — the single-pass learner
        # adapts within the very chunk that drifted.
        # novelty_rate is a host-side statistic: NaN (like mean_ll) when no
        # per-chunk host consumer exists — on the vmem path the failure
        # mask then stays device-side until the lifecycle boundary, and a
        # fake 0.0 would read as "no novelty observed"
        mean_ll = float("nan")
        novelty_rate = float("nan")
        fails = fails_dev = None
        if (need_stats or need_fails) and formed:
            # shortlisted runtimes keep the stats pass sublinear too — a
            # dense (B, K) sweep here would re-introduce the O(K·D²)
            # per-point cost the sparse body just removed.  Keyed on the
            # RESOLVED path (not cfg.shortlist_c): a forced dense path
            # must see dense gate stats or the spawn buffer would collect
            # points the dense gate actually accepted.
            stats = (shortlist.chunk_stats_sparse if self.path == "sparse"
                     else ingest.chunk_stats)
            fails_dev, mean_ll_dev = stats(
                cfg, self.state, xc, self._thresh[0])
            if need_stats:
                fails = np.asarray(fails_dev)
                novelty_rate = float(fails.mean())
                mean_ll = float(mean_ll_dev)

        if path == "vmem":
            self.state, nacc = ingest.fit_chunk_vmem(cfg, self.state, xc)
            self._accepted_dev = self._accepted_dev + nacc   # device add
            if need_fails:
                if fails is not None:        # already pulled for stats
                    if fails.any():
                        self.buffer.push(xc_host[fails])
                elif fails_dev is not None:  # defer to lifecycle boundary
                    self._pending_fails.append((fails_dev, xc_host))
        else:
            # inline creation/§2.3 pruning ⇔ identical to one-shot fit;
            # with lifecycle enabled, pruning is deferred to the pool pass
            do_prune = rc.lifecycle is None and cfg.spmin > 0
            body = (ingest.fit_chunk_sparse if path == "sparse"
                    else ingest.fit_chunk_scan)
            self.state = body(cfg, self.state, xc, do_prune)
        self.state_epoch += 1

        drift_score, alarm = 0.0, False
        if self.detector is not None and mean_ll == mean_ll:
            drift_score, alarm = self.detector.update(
                mean_ll, novelty_rate, weight=xc.shape[0] / rc.chunk)
            if alarm:
                self._respond_to_drift()

        with span("stream.chunk_fence"):
            # the active_k pull doubles as the latency fence: it blocks on
            # this chunk's (donated, async-dispatched) fit, so latency_s
            # includes the device compute on every path — this is the ONE
            # per-chunk device sync the telemetry schema requires
            # (chunk-granular active_k/latency records cannot be deferred
            # without losing them)
            active_k = int(self.state.n_active)
            latency = time.perf_counter() - t0
            self.telemetry.record(telemetry.ChunkMetrics(
                idx=self.chunk_idx, n_points=int(xc.shape[0]),
                active_k=active_k,
                created=int(self.state.n_created) - n_created0,
                mean_ll=mean_ll, novelty_rate=novelty_rate,
                drift_score=float(drift_score), drift_alarm=alarm,
                path=path, latency_s=latency))
            self._m_chunk_s.observe(latency)
            self._m_meas_s.set(latency)
            if self.dispatch.per_point_s is not None:
                # predicted for THIS chunk size — a tail chunk is smaller
                self._m_pred_s.set(self.dispatch.per_point_s
                                   * int(xc.shape[0]))
            self._m_points.inc(int(xc.shape[0]))
            self._m_active.set(active_k)
            if alarm:
                self._m_drift.inc()
        self.chunk_idx += 1

        if (rc.lifecycle is not None and rc.lifecycle.every > 0
                and self.chunk_idx % rc.lifecycle.every == 0):
            self._run_lifecycle()
        if (self.ckpt is not None and rc.checkpoint_every > 0
                and self.chunk_idx % rc.checkpoint_every == 0):
            self.checkpoint()

    # ------------------------------------------------------------------
    # lifecycle / drift plumbing
    # ------------------------------------------------------------------

    def _drain_pending_fails(self) -> None:
        """Materialise the deferred gate-failure masks into the spawn
        buffer (the one place their host rows are actually consumed)."""
        for fails_dev, xc_host in self._pending_fails:
            fails = np.asarray(fails_dev)
            if fails.any():
                self.buffer.push(xc_host[fails])
        self._pending_fails.clear()

    def _fold_accept_counter(self) -> None:
        """Pull the device-side vmem accept counter into telemetry — called
        at lifecycle boundaries and end-of-ingest, never per chunk."""
        n = int(self._accepted_dev)
        if n:
            self.telemetry.add_accepted(n)
            self._accepted_dev = jnp.zeros((), jnp.int32)

    def _run_lifecycle(self, final: bool = False) -> None:
        del final  # the pass is identical; the flag only documents intent
        t0 = time.perf_counter()
        with span("stream.lifecycle") as sp:
            self._drain_pending_fails()
            self._fold_accept_counter()
            self.state, rep = lifecycle.run_pass(
                self.cfg, self.rcfg.lifecycle, self.state, self.buffer)
            self.state_epoch += 1
            sp.set(pruned=rep.pruned, merged=rep.merged,
                   spawned=rep.spawned)
        self.telemetry.add_lifecycle(rep.pruned, rep.merged, rep.spawned)
        self._m_lifecycle_s.observe(time.perf_counter() - t0)
        self._m_active.set(int(self.state.n_active))

    def _respond_to_drift(self) -> None:
        dcfg = self.rcfg.drift
        with span("stream.drift_response", response=dcfg.response):
            if dcfg.response == "fork" and self.ckpt is not None:
                # preserve the pre-drift mixture before mutating it
                self.checkpoint()
            self.state = drift_mod.respond(self.cfg, dcfg, self.state)
            self.state_epoch += 1

    # ------------------------------------------------------------------
    # pool export / import (fleet scale events)
    # ------------------------------------------------------------------

    def export_pool(self) -> FIGMNState:
        """The live mixture, for mass-conserving pool moves (fleet
        autoscaling).  Returns a COPY: the chunk-ingest jits donate the
        live state's buffers (Λ reused in place), so handing out the live
        leaves would let the next ingest invalidate them under the holder
        — the copy keeps the documented promise that an exported pool
        survives further ingestion, bit-identically."""
        return jax.tree_util.tree_map(jnp.copy, self.state)

    def import_pool(self, state: FIGMNState) -> None:
        """Replace the live mixture wholesale (fleet scale events: a split
        half on scale-up, the drained union on scale-down).

        Only the pool changes — the chunk clock, telemetry, drift detector
        and spawn buffer stay; but the drift CUSUM's log-likelihood
        baseline belonged to the OLD pool, so its reference window restarts
        (otherwise losing/gaining half the components reads as a fake
        regime change on the very next chunk).
        """
        want = (self.cfg.kmax, self.cfg.dim)
        got = tuple(int(s) for s in state.mu.shape)
        if got != want:
            raise ValueError(f"pool shape {got} != configured {want}")
        # Defensive copy: the chunk-ingest jits DONATE their state buffers
        # (Λ reused in place across chunks), so the runtime must own every
        # buffer privately — an imported pool may alias the exporter's
        # arrays (e.g. the kept half of an autoscale split), and donating a
        # shared buffer would invalidate it under the other holder.
        self.state = jax.tree_util.tree_map(jnp.copy, state)
        self.state_epoch += 1
        if self.detector is not None:
            self.detector.reset_baseline()

    def reset_state(self) -> None:
        """Recovery of last resort: discard the mixture AND the stream
        clocks (telemetry counters, chunk index, drift state, spawn
        buffer) — what the fleet supervisor does when a crashed replica
        has NO intact checkpoint to restore from.  Every point the
        replica had ever ingested is gone; the caller (supervisor) is
        responsible for accounting them as lost, which is why the
        telemetry reset here must be total — a fresh state with stale
        ``total_points`` would double-count in the mass identity."""
        rc = self.rcfg
        self.state = figmn.init_state(self.cfg)
        self.state_epoch += 1
        self.chunk_idx = 0
        self.buffer = lifecycle.FailureBuffer(
            rc.lifecycle.buffer_cap if rc.lifecycle else 0, self.cfg.dim)
        self.detector = (drift_mod.DriftDetector(rc.drift)
                         if rc.drift else None)
        self.telemetry = telemetry.Telemetry(
            capacity=rc.telemetry_capacity,
            anomaly=AnomalyDetector(dim=3, warmup=16)
            if rc.telemetry_anomaly else None)
        self._accepted_dev = jnp.zeros((), jnp.int32)
        self._pending_fails = []

    # ------------------------------------------------------------------
    # scoring / checkpointing
    # ------------------------------------------------------------------

    def score(self, xs) -> Array:
        """(N,) mixture log-densities under the current state (read-only).

        On a shortlisted runtime (resolved path "sparse") the read path is
        sublinear in K too: one (B, K) bound pass + a (B, C) exact pass
        (core.shortlist.score_batch_sparse) instead of the dense (B, K)
        Mahalanobis sweep.  A forced dense ingest path scores densely —
        reads and writes stay consistent."""
        xs = jnp.asarray(xs, self.cfg.dtype)
        if xs.shape[0] == 0:
            # B=0 contract (shared with predict and every serving
            # frontend): well-formed empty output, no device dispatch
            return jnp.zeros((0,), self.cfg.dtype)
        if self.path == "sparse":
            return shortlist.score_batch_sparse(self.cfg, self.state, xs)
        return ingest.score_batch_jit(self.cfg, self.state, xs)

    def predict(self, xs, targets, return_var: bool = False):
        """(N, o) eq. 27 conditional means of ``targets`` given the rest,
        under the current state (read-only; raises on an empty pool).

        Same path contract as ``score``: a shortlisted runtime serves the
        conditional through ``inference.predict_batch_sparse`` (O(K·D +
        C·D²·o) per point, bit-identical to dense at C ≥ active K), a
        dense one through the batched dense kernel.  The factor stage is
        amortised through the runtime's per-epoch ``FactorCache``: repeat
        reads between state mutations reuse the same bundle,
        bit-identically.  return_var=True additionally returns the (N, o)
        conditional variance as a (mean, var) pair."""
        xs = jnp.asarray(xs, self.cfg.dtype)
        return inference.predict_batch_routed(
            self.cfg, self.state, xs, targets,
            c=self.cfg.shortlist_c if self.path == "sparse" else 0,
            cost_table=self.rcfg.cost_table, device=self.rcfg.device,
            return_var=return_var, factor_cache=self.factor_cache,
            epoch=self.state_epoch)

    def _payload(self) -> Dict[str, object]:
        """Everything a resumed runtime needs to continue bit-identically:
        the mixture, the chunk clock, the drift detector's CUSUM/reference
        window (else a resumed replica re-warms up and misses early
        drift), the running telemetry counters (else the summary resets)
        and the pending gate-failure spawn buffer (else the next lifecycle
        pass spawns different components than the uninterrupted run)."""
        payload = {"figmn": self.state,
                   "runtime": {"chunk_idx":
                               jnp.asarray(self.chunk_idx, jnp.int32)},
                   "telemetry": self.telemetry.export_counters()}
        if self.detector is not None:
            payload["drift"] = self.detector.export_state()
        if self.rcfg.lifecycle is not None:
            payload["spawn_buffer"] = self.buffer.export_state()
        return payload

    def checkpoint(self) -> None:
        if self.ckpt is None:
            raise RuntimeError("no checkpoint_dir configured")
        # deferred device-side residue must land before the payload export
        # (the spawn buffer and telemetry counters are part of it)
        self._drain_pending_fails()
        self._fold_accept_counter()
        self.ckpt.save(self.chunk_idx, self._payload())
        self.ckpt.wait()

    def resume(self, step: Optional[int] = None) -> bool:
        """Restore a checkpoint (latest by default); True if one existed.

        step: restore this exact step instead — the fleet coordinator pins
        per-replica steps in its manifest so a resumed fleet is a
        consistent cut even when replicas auto-checkpointed after the last
        manifest write.
        """
        if self.ckpt is None:
            raise RuntimeError("no checkpoint_dir configured")
        if step is None:
            # newest INTACT step: auto-resume must never pick a payload
            # whose content hashes no longer match its manifest when an
            # earlier verified step exists (crash-recovery semantics)
            step = self.ckpt.latest_step(verify=True)
        elif step not in self.ckpt.all_steps():
            return False
        if step is None:
            return False
        template = {"figmn": figmn.init_state(self.cfg),
                    "runtime": {"chunk_idx": jnp.zeros((), jnp.int32)},
                    "telemetry": telemetry.Telemetry.counters_template()}
        if self.detector is not None:
            template["drift"] = drift_mod.DriftDetector.state_template(
                self.rcfg.drift)
        if self.rcfg.lifecycle is not None:
            template["spawn_buffer"] = lifecycle.FailureBuffer \
                .state_template(self.buffer.cap, self.cfg.dim)
        # missing="template": checkpoints from an older payload format
        # restore what they have; newer sections start fresh (zeros)
        loaded = self.ckpt.restore(step, template, missing="template")
        self.state = loaded["figmn"]
        self.state_epoch += 1
        self.chunk_idx = int(loaded["runtime"]["chunk_idx"])
        self.telemetry.load_counters(loaded["telemetry"])
        if self.detector is not None:
            self.detector.load_state(loaded["drift"])
        if self.rcfg.lifecycle is not None:
            self.buffer.load_state(loaded["spawn_buffer"])
        return True
