"""The one table of device facts, keyed by ``jax.Device.device_kind``.

Every number the program derives from the hardware — roofline peaks
(``obs.prof``, ``benchmarks/roofline.py``) and the VMEM limit of the
resident ingest kernel (``kernels.figmn_stream``, ``stream.ingest``) —
comes from here.  A kind that is not in the table is an error: a peak or a
budget guessed for the wrong chip would silently mislabel a measurement or
launch a kernel the compiler refuses.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Facts about one device kind, with where they come from."""
    kind: str
    flops: float        # peak FLOP/s (bf16 on the MXU for TPUs)
    hbm_bw: float       # peak memory bytes/s
    vmem_bytes: int     # VMEM a Pallas kernel may claim (0: no VMEM)
    source: str


#: ``vmem_bytes`` is the per-core VMEM of the chip, which the v5e compile
#: accepts as a kernel's ``vmem_limit_bytes``; tests/test_tpu_compile.py
#: compiles the resident kernel at the largest size
#: ``stream.ingest.select_path`` admits against it.
DEVICES = {
    "TPU v5 lite": DeviceSpec(
        kind="TPU v5 lite", flops=197e12, hbm_bw=819e9,
        vmem_bytes=128 * 2 ** 20,
        source="Google Cloud documentation, 'TPU v5e' page: 197 TFLOP/s "
               "bf16, 819 GB/s HBM; VMEM: jax.experimental.pallas.tpu "
               "get_tpu_info() for 'TPU v5 lite', 128 MiB per core"),
    "cpu": DeviceSpec(
        kind="cpu", flops=1e11, hbm_bw=3e10, vmem_bytes=0,
        source="coarse single-socket anchors, for CPU attribution only"),
}


class UnknownDeviceError(KeyError):
    """A device kind with no entry in ``DEVICES``."""


def device_kind(platform: Optional[str] = None) -> str:
    """``device_kind`` of the first device of ``platform`` (None: the
    process default backend)."""
    return jax.devices(platform)[0].device_kind


def lookup(kind: str) -> DeviceSpec:
    """The ``DeviceSpec`` for ``kind``; raises ``UnknownDeviceError``."""
    try:
        return DEVICES[kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no device facts for device_kind {kind!r}; add an entry with "
            f"its source to repro.devices.DEVICES") from None
