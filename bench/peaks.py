"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, and
1,600 Gbit/s of chip-to-chip interconnect per chip.  A device missing
from the table is an error, never a default.
"""

from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    bf16_flops: float  # FLOP/s on the matrix unit, bf16 operands
    hbm_bytes_per_s: float


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9),
}


def peaks(device_kind: str) -> Peaks:
    """The peaks of ``device_kind`` as JAX reports it."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to "
                       f"bench/peaks.py with its source") from None
