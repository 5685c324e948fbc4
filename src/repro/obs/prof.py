"""Profiling primitives for the dispatch cost model (stream/costmodel.py).

Three measurement tools, deliberately tiny and dependency-free so every
layer (calibration harness, benchmarks, tests) shares ONE timing
discipline instead of re-inventing it per script:

  ``median_time``  — compile-excluded wall time of a jitted callable:
                     warm-up calls first (compilation + first-touch
                     allocation never pollute a sample), then the median
                     of R repeats, each fenced with
                     ``jax.block_until_ready`` (async dispatch would
                     otherwise time the *enqueue*, not the compute).
                     Arguments are rebuilt per call via a factory — the
                     FIGMN fit jits DONATE their state buffers, so a
                     reused argument would be a use-after-donate.
  ``hlo_cost``     — the analytical twin: lower + compile the same
                     callable and run ``distributed.hlo_analysis`` over
                     the compiled module text → {flops, traffic_bytes,
                     ...}.  Returns None when the path cannot be lowered
                     to plain HLO (e.g. Pallas interpret-mode bodies) —
                     a calibration cell without a prediction is still a
                     valid measurement.
  ``roofline_terms`` — fold an hlo_cost dict against the device kind's
                     peaks (``repro.devices``, the table
                     benchmarks/roofline.py reads too) into the classic
                     two-term roofline: predicted_s = max(flops/peak,
                     bytes/bw), tagged with the binding term — the cost
                     model's path CHOICES come from measured medians,
                     predictions only attribute *why* a path wins.
"""
from __future__ import annotations

import statistics
import time
from typing import Callable, Dict, Optional, Sequence

import jax

from repro import devices
from repro.distributed import hlo_analysis


def median_time(fn: Callable, make_args: Callable[[], Sequence],
                *, repeats: int = 3, warmup: int = 1) -> float:
    """Median compile-excluded wall seconds of ``fn(*make_args())``.

    ``make_args`` runs OUTSIDE the timed region (fresh donated buffers,
    host→device puts); each sample times one call fenced by
    ``block_until_ready`` over the full output tree.
    """
    for _ in range(max(int(warmup), 1)):
        jax.block_until_ready(fn(*make_args()))
    samples = []
    for _ in range(max(int(repeats), 1)):
        args = make_args()
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append(time.perf_counter() - t0)
    return float(statistics.median(samples))


def hlo_cost(fn: Callable, *args) -> Optional[Dict[str, float]]:
    """FLOPs / HBM-traffic of the compiled module for ``fn(*args)``.

    Lowers and compiles WITHOUT executing, then walks the compiled HLO
    text (hlo_analysis — scan bodies multiplied by trip count, fusion
    boundaries as the traffic unit).  None when lowering/compiling or
    parsing fails: custom-call-only modules (Pallas) carry no analysable
    body, and the caller records a measurement-only cell.
    """
    try:
        compiled = jax.jit(fn).lower(*args).compile()
        return hlo_analysis.analyze(compiled.as_text())
    except Exception:
        return None


def roofline_terms(hlo: Optional[Dict[str, float]], device_kind: str
                   ) -> Optional[Dict[str, float]]:
    """→ {compute_s, memory_s, predicted_s, bottleneck} or None; an
    unknown ``device_kind`` raises ``devices.UnknownDeviceError``."""
    if not hlo:
        return None
    peaks = devices.lookup(device_kind)
    compute_s = float(hlo.get("flops", 0.0)) / peaks.flops
    memory_s = float(hlo.get("traffic_bytes", 0.0)) / peaks.hbm_bw
    bottleneck = "compute" if compute_s >= memory_s else "memory"
    return {"compute_s": compute_s, "memory_s": memory_s,
            "predicted_s": max(compute_s, memory_s),
            "bottleneck": bottleneck}
