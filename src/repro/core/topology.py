"""Hardware topology model — the chiplet-system analogue for TPU pods.

The paper's hierarchy (worker core → cluster → group/chiplet → multi-chiplet
2.5D system) maps onto (MXU → TPU chip → ICI pod → multi-pod). This module
holds the constants used by the roofline analysis and the link-level model
used to split collective traffic into intra-pod (ICI, the "NoC/mesh") and
inter-pod (the "D2D link") components.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipSpec:
    """Published peaks of one accelerator chip."""
    name: str
    peak_ops: tuple[tuple[str, float], ...]   # (dtype, op/s per chip)
    hbm_bytes: float
    hbm_bw: float                       # B/s
    ici_link_bw: float                  # B/s per link (~ the paper's D2D PHY bundle)
    ici_links_per_chip: int             # 2D torus: ±x, ±y
    vmem_bytes: float

    def peak(self, dtype: str) -> float:
        """Peak op/s per chip for a compute dtype; a dtype the source does
        not list raises rather than borrowing another dtype's peak."""
        table = dict(self.peak_ops)
        if str(dtype) not in table:
            raise KeyError(f"{self.name}: no published peak for dtype "
                           f"{dtype!r} (known: {sorted(table)})")
        return table[str(dtype)]

    @property
    def peak_bf16_flops(self) -> float:
        return self.peak("bfloat16")


#: Peak table keyed by ``jax.Device.device_kind``. Source: Google Cloud
#: documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
#: 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (4 links of 50 GB/s).
#: v5e has no fp8 MXU, so there is no fp8 entry.
PEAKS: dict[str, ChipSpec] = {
    "TPU v5 lite": ChipSpec(
        name="tpu-v5e",
        peak_ops=(("bfloat16", 197e12), ("int8", 393e12)),
        hbm_bytes=16 * 1024**3,
        hbm_bw=819e9,
        ici_link_bw=50e9,
        ici_links_per_chip=4,
        vmem_bytes=128 * 1024**2,
    ),
}


def chip_spec(device_kind: str) -> ChipSpec:
    """The peak-table entry for a device kind; unknown kinds raise."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak table entry for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


#: The dry-run and analytic roofline target.
CHIP = PEAKS["TPU v5 lite"]


@dataclass(frozen=True)
class PodSpec:
    chip: ChipSpec = CHIP
    chips_x: int = 16
    chips_y: int = 16
    # inter-pod (DCN / "D2D") — slower than ICI, like Occamy's narrow D2D link
    interpod_bw_per_chip: float = 12.5e9  # B/s per chip of pod-to-pod bandwidth

    @property
    def n_chips(self) -> int:
        return self.chips_x * self.chips_y

    @property
    def peak_flops(self) -> float:
        return self.n_chips * self.chip.peak_bf16_flops


POD = PodSpec()


def dtype_peak_flops(dtype: str, device_kind: str = "TPU v5 lite") -> float:
    """Peak op/s per chip of ``device_kind`` for a compute dtype (paper
    Fig. 4b analogue: halving precision, bf16 -> int8, doubles it). Unknown
    devices and dtypes raise."""
    return chip_spec(device_kind).peak(dtype)


def roofline_time(flops: float, bytes_hbm: float, bytes_collective: float,
                  n_chips: int, compute_dtype: str = "bfloat16") -> dict:
    """The three roofline terms (seconds) from the prompt-mandated formulas."""
    peak = dtype_peak_flops(compute_dtype)
    return {
        "compute_s": flops / (n_chips * peak),
        "memory_s": bytes_hbm / (n_chips * CHIP.hbm_bw),
        "collective_s": bytes_collective / (n_chips * CHIP.ici_link_bw),
    }
