"""The yardstick's arithmetic: chip peaks and the roofline. The operations
and bytes that a served model needs are its architecture's cost object
(``shape(c)`` of ``bench/arch/<model_type>.py``), computed from its
published sizes.

Everything here is the benchmark's own, so a change to the program cannot
move it.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s per chip
    hbm_bw: float            # bytes/s per chip
    source: str


#: Published peaks keyed by ``jax.Device.device_kind``. A kind not listed is
#: an error: a benchmark never borrows another chip's peak.
PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bw=819e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s"),
}


def peaks(device_kind: str) -> Peaks:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak table entry for device kind {device_kind!r}"
                       f" (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


def least_time(flops: float, nbytes: float, pk: Peaks) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / pk.bf16_flops, nbytes / pk.hbm_bw)
