"""Serving-path reads from a read-only consolidated snapshot.

Two read families share one contract: ``score``/``score_async`` (mixture
log-densities) and ``predict``/``predict_async`` (eq. 27 conditional
reconstruction — the unified query layer's conditional/label kinds).

The serving contract: a read NEVER touches a live replica.  Replicas
mutate their states on every chunk; a scorer reading them mid-stream would
see a half-drifted mixture and, worse, would serialise reads against
ingestion.  Instead the coordinator *publishes* each consolidated global
mixture here; publication is an atomic reference swap (FIGMNState leaves
are immutable jax arrays, so a published snapshot can never change under a
reader), and every score call reads whichever snapshot was current when it
started.  Ingestion therefore never waits on scoring and scoring never
waits on ingestion — the only synchronisation is one mutex around the
reference swap.

``score_async`` pushes the evaluation onto a worker pool and returns a
future: the serving front door queues scores while the coordinator is mid
ingest (XLA releases the GIL during device compute, so worker-thread
scoring genuinely overlaps host-side routing/lifecycle work).

Serving COST (ROADMAP item 4's amortisation layer):

* The eq. 27 factor stage (W⁻¹Z solve, Schur complement, marginal logdet)
  depends only on (snapshot, targets) — so the frontend keys an
  ``inference.FactorCache`` on (snapshot version, targets signature) and
  every predict against one published snapshot pays factor construction
  once.  The (state, version) pair is captured atomically under the swap
  lock, so a cached bundle can never serve a newer snapshot; results are
  bit-identical to the uncached kernel by construction (same bundle into
  the same jitted batch kernel).
* With an ``AdmissionConfig``, async requests flow through a micro-batcher
  (the slot/queue pattern of ``serve.engine``): compatible queued requests
  — same kind, same targets signature, same return_var — coalesce into ONE
  device dispatch under a max-delay + max-batch policy, and ONE
  device-to-host copy of its output answers them all (each future
  resolves to a read-only NumPy view of the flush's host buffer).  Each
  request's latency is still observed from its OWN submit stamp (queue
  wait + delay + batched compute), so the histogram contract the
  autoscaler consumes is unchanged.  Queue depth and coalesced batch
  size export through the obs registry; a full queue rejects at
  submission (admission control, not silent unbounded buffering).

Scoring cost: the dense read is one (B, K) Mahalanobis sweep over the full
(K, D, D) snapshot — O(B·K·D²).  With a shortlist width C (cfg.shortlist_c
or the ``shortlist_c`` constructor override) the read runs
``core.shortlist.score_batch_sparse`` instead: one tiled (B, K) bound pass
+ a (B, C) exact pass — O(B·K·D + B·C·D²), the serving-side twin of the
sparse ingest path.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import inference, shortlist
from repro.core.types import Array, FIGMNConfig, FIGMNState
from repro.ft.retry import RetryPolicy
from repro.obs import metrics as obs_metrics
from repro.obs import registry as obs_registry
from repro.obs.trace import span
from repro.stream import ingest


class AdmissionRejected(RuntimeError):
    """The admission queue is full.  ``retry_after_s`` is a machine-
    readable backoff hint (the batcher's flush cadence) — clients and the
    frontend's own ``RetryPolicy`` resubmit after it instead of guessing."""

    def __init__(self, msg: str, retry_after_s: float):
        super().__init__(msg)
        self.retry_after_s = float(retry_after_s)


class DeadlineExceeded(TimeoutError):
    """A per-request deadline elapsed (in queue, or by completion)."""


class StalenessExceeded(RuntimeError):
    """The serving snapshot is older than the configured
    ``max_staleness_s`` — degraded serving past its freshness contract."""


@dataclasses.dataclass(frozen=True)
class AdmissionConfig:
    """Micro-batching admission policy for the async read path.

    A request dispatches when its compatibility queue reaches ``max_batch``
    requests OR its oldest entry has waited ``max_delay_s`` — the classic
    latency/throughput knob pair.  ``queue_cap`` bounds TOTAL queued
    requests across all compatibility classes; past it, submission raises
    instead of buffering without bound (reject at the door, the admission
    half of admission control)."""
    max_batch: int = 64
    max_delay_s: float = 2e-3
    queue_cap: int = 1024


class _Pending(NamedTuple):
    xs: Array          # (n, ·) already dtype-normalised
    n: int
    future: "Future"
    t_submit: float    # perf_counter at caller submission (latency stamp)
    t_enq: float       # monotonic at enqueue (max-delay clock)
    deadline_t: Optional[float] = None   # monotonic cutoff (None = no SLO)
    req: int = 0       # the frontend's request number (``serve.submit``)


class _MicroBatcher:
    """Coalesces compatible async reads into single device dispatches.

    One daemon thread owns the flush loop (the revival of
    ``serve.engine``'s slot/queue pattern on the mixture read path):
    requests land in per-compatibility-class deques — key = (kind, targets
    signature, return_var); the frontend's shortlist width is fixed per
    instance so it needs no key slot — and a class flushes when full
    (``max_batch`` requests) or aged (``max_delay_s`` since its oldest
    entry).  The flush concatenates the member batches, runs ONE
    ``_execute`` against the current snapshot, copies its output to the
    host ONCE, splits the rows back out of that host buffer, and resolves
    each future; per-request latency is observed from each request's own
    submit stamp, so queue wait + coalescing delay stay inside the
    histogram the autoscaler watches.  A queued request's future resolves
    to a host ``numpy.ndarray`` (a pair of them under return_var),
    bit-identical to the synchronous read: a read-only view of its flush's
    host buffer, so no caller can alter another's answer.  An empty
    request resolves inline with the synchronous read's (0, ·) arrays.

    Every instant of the flush thread sits inside a span:
    ``serve.wait_request`` (nothing queued), ``serve.coalesce`` (holding
    queued work for the delay/batch policy) or ``serve.flush``, whose
    children are ``serve.gather`` (deadline filter, concatenation),
    ``serve.score``/``serve.predict`` (the device read) and
    ``serve.resolve`` (the flush's one host copy, accounting, futures)."""

    def __init__(self, frontend: "ScoringFrontend", acfg: AdmissionConfig,
                 reg) -> None:
        self._fe = frontend
        self.acfg = acfg
        self._cv = threading.Condition()
        self._queues: "Dict[tuple, deque]" = {}
        self._depth = 0
        self._closed = False
        self._cancel = False
        self._m_depth = reg.gauge(
            "figmn_serve_queue_depth",
            "requests waiting in the micro-batch admission queue")
        self._m_batch_reqs = reg.histogram(
            "figmn_serve_coalesced_requests",
            "requests coalesced into one device dispatch",
            bounds=obs_metrics.log_bounds(1.0, 4096.0))
        self._m_batch_rows = reg.histogram(
            "figmn_serve_coalesced_rows",
            "points per coalesced device dispatch",
            bounds=obs_metrics.log_bounds(1.0, 1_048_576.0))
        self._m_rejected = reg.counter(
            "figmn_serve_admission_rejected_total",
            "requests rejected by the admission queue cap")
        self._m_queue_wait = reg.histogram(
            "figmn_serve_queue_wait_seconds",
            "admission-queue wait: submit to the pop of the request's "
            "batch")
        self._m_resolve_copies = reg.counter(
            "figmn_serve_resolve_copies_total",
            "device-to-host copies made by resolve: one per dispatched "
            "flush")
        self._flushes = 0          # flush sequence number (flush thread)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="fleet-microbatch")
        self._thread.start()

    @property
    def depth(self) -> int:
        with self._cv:
            return self._depth

    def submit(self, kind: str, xs, targets, return_var: bool,
               t_submit: float, deadline_t: Optional[float] = None,
               req: int = 0) -> "Future":
        fe = self._fe
        xs = jnp.asarray(xs, fe.cfg.dtype)
        sig = inference._as_targets(targets) if kind == "predict" else None
        fut: "Future" = Future()
        n = int(xs.shape[0])
        if n == 0:
            # B=0 contract: no device dispatch, nothing to coalesce — run
            # the (dispatch-free) execute inline and resolve immediately.
            out, published_t = fe._execute(kind, xs, targets, return_var)
            fe._finish(kind, 0, t_submit, published_t)
            fut.set_result(out)
            return fut
        with self._cv:
            if self._closed:
                raise RuntimeError("micro-batcher is closed")
            if self._depth >= self.acfg.queue_cap:
                self._m_rejected.inc()
                # one flush cadence is when queue room next appears —
                # the machine-readable backoff hint
                raise AdmissionRejected(
                    f"admission queue full ({self.acfg.queue_cap} requests "
                    "waiting): request rejected — retry after "
                    f"{self.acfg.max_delay_s:g}s or raise "
                    "AdmissionConfig.queue_cap",
                    retry_after_s=self.acfg.max_delay_s)
            key = (kind, sig, bool(return_var))
            self._queues.setdefault(key, deque()).append(
                _Pending(xs, n, fut, t_submit, time.monotonic(),
                         deadline_t, req))
            self._depth += 1
            self._m_depth.set(self._depth)
            self._cv.notify()
        return fut

    def _loop(self) -> None:
        acfg = self.acfg
        while True:
            with self._cv:
                if not self._closed and self._depth == 0:
                    # nothing queued: the device idles by design here
                    with span("serve.wait_request"):
                        while not self._closed and self._depth == 0:
                            self._cv.wait()
                if self._closed and self._cancel:
                    # deterministic shutdown: every queued future resolves
                    # NOW, with CancelledError — no caller blocks forever
                    for dq in self._queues.values():
                        for p in dq:
                            p.future.cancel()
                    self._queues.clear()
                    self._depth = 0
                    self._m_depth.set(0)
                    return
                if self._depth == 0:       # closed and drained
                    return
                # oldest head across classes decides what flushes next
                key = min(self._queues, key=lambda k:
                          self._queues[k][0].t_enq)
                dq = self._queues[key]
                wait = acfg.max_delay_s - (time.monotonic() - dq[0].t_enq)
                if (len(dq) < acfg.max_batch and wait > 0
                        and not self._closed):
                    with span("serve.coalesce"):
                        self._cv.wait(timeout=wait)
                    continue
                batch = [dq.popleft()
                         for _ in range(min(len(dq), acfg.max_batch))]
                t_pop = time.perf_counter()
                if not dq:
                    del self._queues[key]
                self._depth -= len(batch)
                self._m_depth.set(self._depth)
            self._flush(key, batch, t_pop)

    def _flush(self, key: tuple, batch: "List[_Pending]",
               t_pop: float) -> None:
        kind, sig, return_var = key
        fe = self._fe
        self._flushes += 1
        with span("serve.flush", kind=kind, flush=self._flushes,
                  req=batch[0].req) as sp:
            with span("serve.gather"):
                # expired deadlines resolve exceptionally BEFORE the
                # dispatch — no device work is spent on an answer nobody
                # is waiting for
                now = time.monotonic()
                live = []
                for p in batch:
                    self._m_queue_wait.observe(t_pop - p.t_submit)
                    if p.deadline_t is not None and now > p.deadline_t:
                        p.future.set_exception(DeadlineExceeded(
                            f"request deadline elapsed after "
                            f"{now - p.t_enq:.4f}s in queue"))
                    else:
                        live.append(p)
                batch = live
                if not batch:
                    return
                xs = (batch[0].xs if len(batch) == 1
                      else jnp.concatenate([p.xs for p in batch], axis=0))
            sp.set(requests=len(batch), rows=int(xs.shape[0]))
            self._m_batch_reqs.observe(len(batch))
            self._m_batch_rows.observe(int(xs.shape[0]))
            try:
                out, published_t = fe._execute(kind, xs, sig, return_var)
            except Exception as e:               # pragma: no cover - defensive
                for p in batch:
                    p.future.set_exception(e)
                return
            with span("serve.resolve"):
                # ONE device-to-host copy per flush (both members of a
                # return_var pair in one call); every answer is then a
                # NumPy view of that read-only buffer — no device program
                # and no transfer per answer
                try:
                    host = jax.device_get(out)
                except Exception as e:
                    for p in batch:
                        p.future.set_exception(e)
                    return
                self._m_resolve_copies.inc()
                off = 0
                for p in batch:
                    rows = slice(off, off + p.n)
                    res = ((host[0][rows], host[1][rows]) if return_var
                           else host[rows])
                    off += p.n
                    fe._finish(kind, p.n, p.t_submit, published_t)
                    p.future.set_result(res)

    def close(self, cancel_pending: bool = False) -> None:
        """Stop the flush thread.  Default drains (every queued future
        resolves with its result); ``cancel_pending=True`` resolves every
        queued future with CancelledError instead — either way, no future
        is left dangling."""
        with self._cv:
            self._closed = True
            self._cancel = cancel_pending
            self._cv.notify_all()
        self._thread.join()


class ScoringFrontend:
    """Read-only mixture scores from the last published snapshot.

    Observability contract (the read path's half of the serving→autoscaler
    loop): every request lands one sample in ``latency`` — a mergeable
    fixed-log-bucket histogram whose cumulative snapshots the coordinator
    diffs between consolidation boundaries to hand the autoscaler a
    *windowed* p99/QPS (``autoscale.ServingSignal``).  Async requests time
    submit→completion, so queue wait under an overloaded worker pool (and,
    with admission control, micro-batch coalescing delay) is part of the
    measured latency — exactly the signal an operator (or the autoscaler)
    pages on.  ``staleness`` records the age of the serving snapshot at
    read time: how far behind the live stream each answer is.
    """

    def __init__(self, cfg: FIGMNConfig, workers: int = 2,
                 shortlist_c: Optional[int] = None,
                 registry: Optional[obs_registry.Registry] = None,
                 cost_table=None, device: Optional[str] = None,
                 admission: Optional[AdmissionConfig] = None,
                 factor_cache_size: int = 16,
                 max_staleness_s: Optional[float] = None,
                 retry: Optional[RetryPolicy] = None):
        self.cfg = cfg
        # serving degradation contract: during fleet recovery reads keep
        # answering from the last good snapshot, but never one older than
        # max_staleness_s (None = unbounded); retry resubmits async
        # requests bounced by admission control (budgeted backoff+jitter)
        self.max_staleness_s = max_staleness_s
        self.retry = retry
        self._degraded_reason: Optional[str] = None
        # serving-side shortlist width: explicit override wins, else the
        # config's; 0 ⇒ dense scoring
        self.shortlist_c = int(cfg.shortlist_c if shortlist_c is None
                               else shortlist_c)
        # measured predict routing (stream.costmodel): with a calibrated
        # table the dense/sparse eq. 27 switch follows the measured winner
        # per request size; None ⇒ the historical shortlist_c rule
        self.cost_table = cost_table
        self.device = device
        self._lock = threading.Lock()
        self._snapshot: Optional[FIGMNState] = None
        self._version = 0
        self._published_t: Optional[float] = None
        self._pool = ThreadPoolExecutor(max_workers=max(int(workers), 1),
                                        thread_name_prefix="fleet-score")
        self.served = 0
        self._reqs = itertools.count(1)     # request numbers (serve.submit)
        reg = registry or obs_registry.default_registry()
        # per-(version, targets) eq. 27 factor amortisation — invalidation
        # rides the version bump inside publish's atomic swap
        self.factor_cache = inference.FactorCache(factor_cache_size,
                                                  registry=reg)
        self.latency = reg.histogram(
            "figmn_serve_latency_seconds",
            "request latency, submit to completion: with admission "
            "control, the queue wait plus the flush that answers it")
        self.staleness = reg.histogram(
            "figmn_serve_staleness_seconds",
            "serving-snapshot age at read time",
            bounds=obs_metrics.log_bounds(1e-4, 1000.0))
        self._m_requests = {
            kind: reg.counter("figmn_serve_requests_total",
                              "serving requests completed",
                              {"kind": kind})
            for kind in ("score", "predict")}
        self._m_points = reg.counter(
            "figmn_serve_points_total", "points scored/predicted")
        self._m_degraded_total = reg.counter(
            "figmn_serve_degraded_total",
            "requests answered from the last good snapshot while the "
            "fleet was recovering")
        self._m_degraded = reg.gauge(
            "figmn_serve_degraded",
            "1 while serving is in degraded mode (fleet recovering)")
        self.batcher: Optional[_MicroBatcher] = (
            _MicroBatcher(self, admission, reg)
            if admission is not None else None)

    @property
    def requests_total(self) -> int:
        """Cumulative completed requests across kinds (the QPS numerator
        the autoscaler deltas)."""
        return int(sum(c.value for c in self._m_requests.values()))

    # -- publication (coordinator side) --------------------------------

    def publish(self, state: FIGMNState, version: Optional[int] = None
                ) -> int:
        """Swap in a new snapshot; returns its version number.

        The version bump IS the factor-cache invalidation: reads key the
        eq. 27 ``FactorCache`` on the version captured with the state
        under this same lock, so requests against the new snapshot miss
        onto fresh factors and stale bundles age out of the LRU."""
        with self._lock:
            self._version = self._version + 1 if version is None \
                else int(version)
            self._snapshot = state
            self._published_t = time.monotonic()
            return self._version

    @property
    def version(self) -> int:
        return self._version

    # -- degraded mode (supervisor side) --------------------------------

    def set_degraded(self, reason: str) -> None:
        """Enter degraded serving: reads keep answering from the last
        good snapshot (subject to ``max_staleness_s``) and are counted
        under ``figmn_serve_degraded_total``.  Called by the supervisor
        at quarantine; idempotent (first reason wins until cleared)."""
        if self._degraded_reason is None:
            self._degraded_reason = reason
        self._m_degraded.set(1)

    def clear_degraded(self) -> None:
        self._degraded_reason = None
        self._m_degraded.set(0)

    @property
    def degraded(self) -> bool:
        return self._degraded_reason is not None

    @property
    def degraded_reason(self) -> Optional[str]:
        return self._degraded_reason

    @property
    def ready(self) -> bool:
        return self._snapshot is not None

    def snapshot(self) -> Tuple[Optional[FIGMNState], int]:
        """The current (state, version) pair under the swap lock."""
        with self._lock:
            return self._snapshot, self._version

    # -- reads (serving side) ------------------------------------------

    def _execute(self, kind: str, xs, targets, return_var: bool = False):
        """One device dispatch against an atomically-captured snapshot.

        Returns (out, published_t).  The (state, version) pair is read
        under the swap lock so the factor cache can never pair a cached
        bundle with a different snapshot's state.  B=0 returns well-formed
        (0, ·) outputs with NO device dispatch — the one empty-batch
        contract every frontend shares (see inference._empty_result)."""
        with self._lock:
            state = self._snapshot
            version = self._version
            published_t = self._published_t
        if state is None:
            raise RuntimeError("no consolidated snapshot published yet")
        if (self.max_staleness_s is not None and published_t is not None):
            age = time.monotonic() - published_t
            if age > self.max_staleness_s:
                raise StalenessExceeded(
                    f"serving snapshot is {age:.3f}s old (bound "
                    f"{self.max_staleness_s:g}s)"
                    + (f"; degraded: {self._degraded_reason}"
                       if self._degraded_reason else ""))
        xs = jnp.asarray(xs, self.cfg.dtype)
        with span(f"serve.{kind}", n=int(xs.shape[0])):
            if kind == "score":
                if xs.shape[0] == 0:
                    out = jnp.zeros((0,), self.cfg.dtype)
                elif self.shortlist_c > 0:
                    out = shortlist.score_batch_sparse(
                        self.cfg, state, xs, c=self.shortlist_c)
                else:
                    out = ingest.score_batch_jit(self.cfg, state, xs)
            else:
                out = inference.predict_batch_routed(
                    self.cfg, state, xs, targets, c=self.shortlist_c,
                    cost_table=self.cost_table, device=self.device,
                    return_var=return_var,
                    factor_cache=self.factor_cache, epoch=version)
            lead = out[0] if isinstance(out, tuple) else out
            if lead.shape[0]:
                lead.block_until_ready()   # latency must cover compute
        return out, published_t

    def _finish(self, kind: str, n: int, t_submit: float,
                published_t: Optional[float]) -> None:
        """Per-request accounting.  ``t_submit`` is the caller-side submit
        stamp: for sync reads it equals entry time (pure service latency);
        for async reads it was taken at ``submit``, so the measured
        latency INCLUDES queue wait (worker pool or micro-batch) — the
        component that actually blows up under overload."""
        self.latency.observe(time.perf_counter() - t_submit)
        if published_t is not None:
            self.staleness.observe(time.monotonic() - published_t)
        if self._degraded_reason is not None:
            self._m_degraded_total.inc()
        self._m_requests[kind].inc()
        self._m_points.inc(n)
        with self._lock:        # += races across pool threads otherwise
            self.served += n

    def _serve(self, kind: str, xs, targets, t_submit: float,
               return_var: bool = False,
               deadline_s: Optional[float] = None):
        """One timed read: execute + accounting.  A ``deadline_s`` turns
        an SLO miss into DeadlineExceeded AFTER accounting (the latency
        sample still lands — overload must stay visible to the
        autoscaler even when callers give up)."""
        out, published_t = self._execute(kind, xs, targets, return_var)
        lead = out[0] if isinstance(out, tuple) else out
        elapsed = time.perf_counter() - t_submit
        self._finish(kind, int(lead.shape[0]), t_submit, published_t)
        if deadline_s is not None and elapsed > deadline_s:
            raise DeadlineExceeded(
                f"{kind} completed in {elapsed:.4f}s > deadline "
                f"{deadline_s:g}s")
        return out

    def _submit_async(self, kind: str, xs, targets, return_var: bool,
                      deadline_s: Optional[float]) -> "Future":
        t = time.perf_counter()
        req = next(self._reqs)
        with span("serve.submit", kind=kind, req=req):
            if self.batcher is not None:
                deadline_t = (time.monotonic() + deadline_s
                              if deadline_s is not None else None)

                def _try():
                    return self.batcher.submit(kind, xs, targets,
                                               return_var, t, deadline_t,
                                               req)

                if self.retry is not None:
                    return self.retry.call(_try, retry_on=AdmissionRejected)
                return _try()
            return self._pool.submit(self._serve, kind, xs, targets, t,
                                     return_var, deadline_s)

    def score(self, xs, deadline_s: Optional[float] = None) -> Array:
        """(N,) mixture log-densities under the current snapshot."""
        return self._serve("score", xs, None, time.perf_counter(),
                           deadline_s=deadline_s)

    def score_async(self, xs, deadline_s: Optional[float] = None
                    ) -> "Future[Array | np.ndarray]":
        """Queue a score; the returned future resolves off the caller's
        thread, against whichever snapshot is current when it runs.  With
        admission control configured, compatible queued scores coalesce
        into one device dispatch and the future resolves to a read-only
        host ``numpy.ndarray`` (bit-identical to ``score``); a request
        still queued when its ``deadline_s`` elapses resolves with
        DeadlineExceeded instead of spending device work.  Without it the
        worker pool resolves the future to ``score``'s device array."""
        return self._submit_async("score", xs, None, False, deadline_s)

    def predict(self, xs, targets, return_var: bool = False,
                deadline_s: Optional[float] = None):
        """(N, o) eq. 27 conditional means under the current snapshot.

        Same serving contract as ``score``: snapshot-atomic (the state is
        captured once under the swap lock; a concurrent publish cannot
        tear the read), never blocks or mutates ingesting replicas, and
        honours the frontend's resolved read path — a shortlist width C
        serves the conditional sublinearly (O(K·D + C·D²·o) per point,
        bit-identical to dense at C ≥ active K).  The factor stage is
        amortised per (snapshot version, targets) through the frontend's
        ``FactorCache`` — bit-identically.  return_var=True additionally
        returns the (N, o) conditional variance as a (mean, var) pair."""
        return self._serve("predict", xs, targets, time.perf_counter(),
                           return_var, deadline_s=deadline_s)

    def predict_async(self, xs, targets, return_var: bool = False,
                      deadline_s: Optional[float] = None
                      ) -> "Future[Array | np.ndarray]":
        """Queue a conditional read; resolves off the caller's thread
        against whichever snapshot is current when it runs — the serving
        front door keeps answering eq. 27 while the coordinator is mid
        ingest.  With admission control configured, compatible queued
        requests (same targets, same return_var) coalesce into one device
        dispatch and the future resolves to read-only host
        ``numpy.ndarray``s (a (mean, var) pair under return_var),
        bit-identical to ``predict``; expired deadlines resolve with
        DeadlineExceeded before any device work.  Without it the worker
        pool resolves the future to ``predict``'s device arrays."""
        return self._submit_async("predict", xs, targets, return_var,
                                  deadline_s)

    def close(self, cancel_pending: bool = False) -> None:
        """Shut the read path down with every pending future resolved
        deterministically: the default drains (queued work completes and
        resolves with results); ``cancel_pending=True`` resolves queued
        futures with CancelledError instead.  In-flight device work
        always runs to completion — only un-started work is cancelled."""
        if self.batcher is not None:
            self.batcher.close(cancel_pending)
        self._pool.shutdown(wait=True, cancel_futures=cancel_pending)
