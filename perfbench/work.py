"""The work the algorithm needs, counted from its sizes, not from the HLO.

A roofline share is the least time the chip could take for the work,
over the device time of the programs that did it.  The least time is the
larger of FLOPs over the peak and bytes over the bandwidth.  Both counts
come from the algorithm (Pinto & Engel 2015, §3), so they read the same
whatever implements it:

* FLOPs.  A learning step computes y = Λ_k e and the rank-one update of
  Λ_k for every active component: 4·K·D² per point.  A read computes one
  quadratic form per active component: 2·K·D² per row.
* Bytes.  The distinct float32 state a step has to read: the symmetric
  half of each Λ_k, μ_k and three scalars per component (log|C|, sp, v).
  It is read once per point (ingest) or once per dispatch (reads), less
  what the device's VMEM could keep between them, floored at zero; the
  rows in and the answers out come on top.

K is the number of active components, which the harness reads from the
state, never ``kmax``.  With these counts a share cannot pass 100%: a
packed symmetric Λ, a VMEM-resident body or a kernel that skips inactive
slots all still do at least this work.
"""
from __future__ import annotations

F32 = 4


def state_bytes(k_active: int, dim: int) -> int:
    """Bytes of the distinct float32 state of ``k_active`` components."""
    return F32 * k_active * (dim * (dim + 1) // 2 + dim + 3)


def ingest_flops(k_active: int, dim: int, points: int) -> float:
    return 4.0 * k_active * dim * dim * points


def ingest_bytes(k_active: int, dim: int, points: int,
                 vmem_bytes: int) -> float:
    per_point = max(state_bytes(k_active, dim) - vmem_bytes, 0)
    return float(points) * (per_point + F32 * dim)


def read_flops(k_active: int, dim: int, rows: int) -> float:
    return 2.0 * k_active * dim * dim * rows


def read_bytes(k_active: int, dim: int, dispatches: int, io_values: int,
               vmem_bytes: int) -> float:
    """``io_values``: float32 values of the rows in and the answers out."""
    per_dispatch = max(state_bytes(k_active, dim) - vmem_bytes, 0)
    return float(dispatches) * per_dispatch + F32 * float(io_values)


def least_seconds(flops: float, nbytes: float, peaks) -> float:
    """The roofline's lower bound on device time for this work."""
    return max(flops / peaks.flops, nbytes / peaks.hbm_bw)


def roofline_pct(flops: float, nbytes: float, device_s: float, peaks):
    """Share of the roofline, in %; None when no device time was seen."""
    if device_s <= 0:
        return None
    return 100.0 * least_seconds(flops, nbytes, peaks) / device_s
