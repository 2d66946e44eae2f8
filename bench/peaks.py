"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` string JAX reports.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.  A v5e
reports its ``device_kind`` as "TPU v5 lite".  A device that is not in the
table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float          # FLOP/s
    int8_ops: float            # OP/s
    hbm_bytes: float           # bytes of device memory
    hbm_bw: float              # bytes/s
    source: str


_V5E = Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bytes=16e9,
             hbm_bw=819e9,
             source="Google Cloud documentation, 'TPU v5e'")

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


class UnknownDevice(RuntimeError):
    """The device kind has no row in ``PEAKS``."""


def for_kind(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}") from None
