"""Shared arithmetic of the per-layer metric readers in ``metrics/``.

A reader gets the run's context: ``trace`` (the reduced profile of the
window, or None), ``device_kind``, ``window_compiles`` and what the
driver counted (``dim``, ``k_active``, ``points``, ``chunks``, ``rows``,
``io_values``, ``dispatches``, ``coalesced_rows_sum``).  It returns a
number, or None where it finds nothing to read.
"""
from __future__ import annotations

from typing import Optional, Sequence

from perfbench import peaks, tracing, work

#: the jit names of the ingest bodies (scan, shortlist, VMEM kernel)
INGEST_PROGRAMS = ("jit_fit", "fit_sparse", "figmn_stream")
#: the jit names of the read kernels and the eq. 27 factor stage
READ_PROGRAMS = ("score_batch", "predict_dense", "predict_sparse",
                 "factors")


def idle_pct(ctx) -> Optional[float]:
    t = ctx.get("trace")
    if not t or not t["trace"].devices or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def program_seconds(ctx, patterns: Sequence[str]) -> float:
    t = ctx.get("trace")
    return tracing.matching_seconds(t["programs"], patterns) if t else 0.0


def device_peaks(ctx) -> peaks.Peaks:
    return peaks.lookup(ctx["device_kind"])


def ingest_roofline_pct(ctx) -> Optional[float]:
    secs = program_seconds(ctx, INGEST_PROGRAMS)
    if not secs or not ctx.get("points"):
        return None
    pk = device_peaks(ctx)
    k, d, n = ctx["k_active"], ctx["dim"], ctx["points"]
    return work.roofline_pct(work.ingest_flops(k, d, n),
                             work.ingest_bytes(k, d, n, pk.vmem_bytes),
                             secs, pk)


def read_roofline_pct(ctx) -> Optional[float]:
    secs = program_seconds(ctx, READ_PROGRAMS)
    if not secs or not ctx.get("dispatches"):
        return None
    pk = device_peaks(ctx)
    k, d = ctx["k_active"], ctx["dim"]
    return work.roofline_pct(
        work.read_flops(k, d, ctx["rows"]),
        work.read_bytes(k, d, ctx["dispatches"], ctx["io_values"],
                        pk.vmem_bytes),
        secs, pk)


def host_gap_ms_per_chunk(ctx) -> Optional[float]:
    t = ctx.get("trace")
    if not t or not ctx.get("chunks"):
        return None
    runs = tracing.program_events(t["trace"], t["win"], INGEST_PROGRAMS)
    if not runs:
        return None
    return tracing.idle_between(t["trace"], t["win"], runs) * 1e-6 \
        / ctx["chunks"]
