"""Published peaks of the devices the benchmark runs on, keyed by
``jax.Device.device_kind``.

The benchmark keeps its own table so that no change to the program can
move the yardstick.  A kind that is not in the table is an error: a
roofline share against the wrong chip's peaks would be a wrong number.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    kind: str
    flops: float        # peak FLOP/s of the matrix unit (bf16)
    hbm_bw: float       # peak HBM bytes/s
    vmem_bytes: int     # on-chip vector memory one core can hold
    source: str


#: The v5e publishes no float32 peak; the work runs in float32, so its
#: bf16 peak makes the compute term of a roofline a true lower bound.
PEAKS = {
    "TPU v5 lite": Peaks(
        kind="TPU v5 lite", flops=197e12, hbm_bw=819e9,
        vmem_bytes=128 * 2 ** 20,
        source="Google Cloud documentation, 'TPU v5e' page: 197 TFLOP/s "
               "bf16, 819 GB/s HBM, 16 GB HBM; VMEM 128 MiB per core "
               "(jax.experimental.pallas.tpu get_tpu_info)"),
}


class UnknownDevice(KeyError):
    """A device kind with no published peaks in ``PEAKS``."""


def lookup(kind: str) -> Peaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {kind!r}; add the kind "
            f"with its source to perfbench/peaks.py") from None
