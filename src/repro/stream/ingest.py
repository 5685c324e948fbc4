"""Micro-batch ingestion: chunking, double-buffered H2D, path dispatch.

Implements the stream side of Algorithm 1: the learner itself is strictly
sequential in the data (that IS the IGMN), so the only ingestion freedoms
are (a) when host→device transfers happen and (b) which compiled body
consumes a chunk.  Two bodies exist:

  "scan"  — per-chunk ``lax.scan`` over ``core.figmn.learn_one`` (the
            reference O(KD²) path, eqs. 3–10/20–26; handles creation and
            pruning inline, so chunked ingestion is bit-identical to one
            ``core.figmn.fit`` call over the concatenated stream),
  "vmem"  — the VMEM-resident Pallas kernel ``kernels.figmn_stream``: the
            whole (K, D, D) working set stays in VMEM scratch for the whole
            chunk and HBM is touched only for the x_t vectors (DESIGN
            lineage in the kernel's module docstring).  Creation events are
            no-ops inside the kernel; gate-failing points are surfaced to
            the caller for the lifecycle spawn buffer.
  "sparse"— the top-C shortlist body (``core.shortlist.fit_sparse``):
            per point an O(K·D) bound pass selects C candidate components
            and the exact O(D²) work (matvec, posterior, fused rank-one
            update) runs on the C gathered rows only — O(K·D + C·D²)
            instead of O(K·D²).  Handles creation and pruning inline like
            "scan" and is BIT-IDENTICAL to it when C ≥ active K.

``select_path`` picks between them: the sparse body whenever the config
enables a shortlist (cfg.shortlist_c > 0 — the biggest K-scaling lever),
else the vmem kernel when the platform is a TPU, the update mode is the
PSD-safe "exact" one and everything the kernel holds in VMEM
(``figmn_stream.vmem_bytes`` at the chunk size) fits the device's VMEM
(``repro.devices``), else the scan reference.
"""
from __future__ import annotations

from functools import partial
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import devices
from repro.core import figmn, shortlist
from repro.core.types import Array, FIGMNConfig, FIGMNState, chi2_quantile
from repro.kernels import figmn_stream


def vmem_budget_for(platform: Optional[str] = None) -> int:
    """VMEM bytes the resident kernel may use on ``platform``'s device
    (None: the default backend), from ``repro.devices``; 0 where the
    device has no VMEM.  An unknown device kind raises."""
    return devices.lookup(devices.device_kind(platform)).vmem_bytes


def vmem_fits(cfg: FIGMNConfig, budget: int, chunk: int) -> bool:
    """The launch guard of the vmem kernel: exact update mode, and its
    VMEM footprint at this chunk size within ``budget``."""
    return (cfg.update_mode == "exact"
            and figmn_stream.vmem_bytes(cfg.kmax, cfg.dim, chunk) <= budget)


def select_path(cfg: FIGMNConfig, *,
                vmem_budget: Optional[int] = None,
                requested: str = "auto",
                device: Optional[str] = None,
                chunk: int = 256) -> str:
    """Choose the per-chunk dispatch path ("scan" | "vmem" | "sparse").

    requested: "scan"/"vmem"/"sparse" force a path; "auto" applies the
    heuristic.  A forced "sparse" requires cfg.shortlist_c > 0 (the width
    is a config property, not a runtime knob — jitted shapes depend on it).
    device: explicit backend platform ("cpu"/"tpu") the decision is for;
    None keys off the process default backend.  vmem_budget: bytes the
    kernel may use; None takes the device's entry in ``repro.devices``.
    chunk: points per dispatch (the kernel holds the chunk in VMEM).
    This is the pure HEURISTIC; the measured, table-driven resolution
    lives in ``stream.costmodel`` and falls back here bit-compatibly when
    no calibration table exists.
    """
    if requested == "sparse" or (requested == "auto"
                                 and cfg.shortlist_c > 0):
        if cfg.shortlist_c <= 0:
            raise ValueError(
                "path 'sparse' requires cfg.shortlist_c > 0")
        return "sparse"
    if requested in ("scan", "vmem"):
        return requested
    if requested != "auto":
        raise ValueError(f"unknown path {requested!r}")
    backend = device if device else jax.default_backend()
    if backend != "tpu":
        return "scan"
    if vmem_budget is None:
        vmem_budget = vmem_budget_for(backend)
    return "vmem" if vmem_fits(cfg, vmem_budget, chunk) else "scan"


NONFINITE_POLICIES = ("drop", "reject", "raise")


class NonFiniteChunkError(ValueError):
    """A chunk carried NaN/Inf rows under ``on_nonfinite="raise"``."""


def finite_guard(xc_host: np.ndarray, policy: str = "drop"
                 ) -> Tuple[np.ndarray, int]:
    """Quarantine non-finite rows BEFORE they can touch Λ.

    One NaN coordinate reaching the rank-one update poisons a component's
    (mu, Λ, logdet) forever — and, through consolidation, the global
    snapshot; the single-pass design has no replay to heal from.  So the
    guard runs on the host chunk ahead of every device dispatch:

      "drop"   keep only the finite rows (per-row quarantine).  Since
               chunking never changes the math (the PR-1 invariant), the
               resulting state is bit-identical to ingesting a stream
               that never contained the poisoned rows.
      "reject" quarantine the WHOLE chunk (a poisoned producer is not
               trusted for the rest of its batch).
      "raise"  raise NonFiniteChunkError (strict pipelines that must
               halt on corrupt input).

    Returns ``(kept_rows, n_quarantined)``.  The all-finite fast path
    returns the input array UNTOUCHED (same object) so the runtime can
    keep using the already-in-flight device copy — zero overhead beyond
    one vectorised isfinite sweep.
    """
    if policy not in NONFINITE_POLICIES:
        raise ValueError(
            f"on_nonfinite must be one of {NONFINITE_POLICIES}")
    finite = np.isfinite(xc_host).all(axis=1)
    if finite.all():
        return xc_host, 0
    if policy == "raise":
        bad = int((~finite).sum())
        raise NonFiniteChunkError(
            f"{bad}/{xc_host.shape[0]} non-finite rows in chunk "
            f"(on_nonfinite='raise')")
    if policy == "reject":
        return xc_host[:0], int(xc_host.shape[0])
    return xc_host[finite], int((~finite).sum())


class DoubleBufferedLoader:
    """Chunked host→device feed with one chunk of transfer lookahead.

    ``jax.device_put`` is asynchronous: issuing the put for chunk i+1 before
    the consumer blocks on chunk i overlaps the H2D copy with the device
    compute on the current chunk — the classic double buffer, with XLA's
    transfer engine as the second buffer.
    """

    def __init__(self, xs, chunk: int, dtype=jnp.float32):
        self._np = np.asarray(xs)
        if self._np.ndim != 2:
            raise ValueError(f"expected (N, D) stream, got {self._np.shape}")
        self.chunk = int(chunk)
        self.dtype = dtype

    def __len__(self) -> int:
        return -(-self._np.shape[0] // self.chunk) if self._np.size else 0

    def _put(self, a: int, b: int) -> Array:
        return jax.device_put(jnp.asarray(self._np[a:b], self.dtype))

    def __iter__(self) -> Iterator[Tuple[Array, np.ndarray]]:
        """Yields (device_chunk, host_chunk) pairs in stream order."""
        n = self._np.shape[0]
        bounds = [(i, min(i + self.chunk, n))
                  for i in range(0, n, self.chunk)]
        if not bounds:
            return
        nxt = self._put(*bounds[0])
        for j, (a, b) in enumerate(bounds):
            cur = nxt
            if j + 1 < len(bounds):
                nxt = self._put(*bounds[j + 1])   # overlap with consumer
            yield cur, self._np[a:b]


def fit_chunk_scan(cfg: FIGMNConfig, state: FIGMNState, xc: Array,
                   do_prune: bool) -> FIGMNState:
    """Reference path: lax.scan of learn_one — identical math to figmn.fit.

    ``figmn.fit`` donates the state, so the (K, D, D) Λ buffer is reused
    in place across chunks; callers must rebind (the runtime does).
    """
    return figmn.fit(cfg, state, xc, do_prune=do_prune)


def fit_chunk_sparse(cfg: FIGMNConfig, state: FIGMNState, xc: Array,
                     do_prune: bool) -> FIGMNState:
    """Shortlist path: top-C sparse scan — bit-identical to "scan" when
    cfg.shortlist_c ≥ active K, O(K·D + C·D²) per point otherwise.  Also
    donates the state like the scan body."""
    return shortlist.fit_sparse(cfg, state, xc, do_prune=do_prune)


def fit_chunk_vmem(cfg: FIGMNConfig, state: FIGMNState, xc: Array
                   ) -> Tuple[FIGMNState, Array]:
    """VMEM-resident path: whole chunk in one pallas_call, compiled for
    the default device with its VMEM limit from ``repro.devices`` (the
    kernel body is interpreted on the CPU).

    Creation events are no-ops inside the kernel (gate-failing points leave
    the state untouched); the caller collects them via ``chunk_stats`` for
    the lifecycle spawn buffer.  Returns (state', n_accepted) with the
    accept counter left ON DEVICE — pulling it here would block the host
    on every chunk; the runtime folds it into telemetry at lifecycle
    boundaries instead.
    """
    n = int(xc.shape[0])
    thresh = jnp.asarray(
        [float(chi2_quantile(cfg.dim, 1.0 - cfg.beta))], jnp.float32)
    on_cpu = jax.default_backend() == "cpu"
    mu, lam, logdet, sp, nacc = figmn_stream.figmn_stream_pallas(
        xc.astype(jnp.float32), state.mu.astype(jnp.float32),
        state.lam.astype(jnp.float32), state.logdet.astype(jnp.float32),
        state.sp.astype(jnp.float32), state.active.astype(jnp.int32),
        thresh, dim=cfg.dim, n_points=n, interpret=on_cpu,
        vmem_limit=None if on_cpu else vmem_budget_for())
    dt = cfg.dtype
    new = FIGMNState(
        mu=mu.astype(dt), lam=lam.astype(dt), logdet=logdet.astype(dt),
        sp=sp.astype(dt),
        # eq. 4: every active component ages once per point
        v=state.v + n * state.active.astype(dt),
        active=state.active, n_created=state.n_created)
    return new, nacc[0]


@jax.jit
def chunk_stats(cfg: FIGMNConfig, state: FIGMNState, xc: Array,
                thresh: Array) -> Tuple[Array, Array]:
    """(fails (B,) bool, mean mixture log-likelihood ()) vs frozen params.

    ONE batched pass over Λ (``figmn.log_joint_batch`` — the same
    implementation ``figmn.score_batch`` reduces) yields d² (B, K), which
    feeds BOTH the chi² gate (→ lifecycle spawn buffer / novelty rate) and
    the mixture log-density (→ drift CUSUM): enabling drift detection
    costs a single extra Λ read per chunk, not one per statistic.
    """
    d2, logjoint = figmn.log_joint_batch(cfg, state, xc)
    fails = ~jnp.any(state.active[None, :] & (d2 < thresh), axis=1)
    ll = jax.scipy.special.logsumexp(logjoint, axis=1)
    return fails, jnp.mean(ll)


learn_one_jit = jax.jit(figmn.learn_one, static_argnames=("do_prune",))

score_batch_jit = jax.jit(figmn.score_batch)
