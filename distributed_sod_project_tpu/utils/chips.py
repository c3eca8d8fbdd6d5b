"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The ONE table every share-of-peak in the package divides by
(utils/capacity.py's live ledger, tools/roofline.py's offline
model) and the scoped-VMEM rule reads
(pallas/vmem_budget.py).  A kind that is not in the table is an error,
never a default: a share of the wrong chip's peak is worse than none.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float   # dense bf16 FLOP/s (MACs*2)
    hbm_bw: float       # bytes/s
    hbm_bytes: float
    ici_bw: float       # bytes/s, aggregate chip-to-chip per chip
    vmem_bytes: int     # physical VMEM per core
    source: str


CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9, ici_bw=2e11,
        vmem_bytes=128 * 1024 * 1024,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
               "bf16, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s ICI; VMEM "
               "128 MiB as the v5e compiler reports it"),
}

# ~100 Gbit/s per-host NIC — the inter-host hop hierarchical
# collectives price.  A property of the host network, not of a chip.
DCN_BW = 12.5e9


class UnknownChipError(ValueError):
    """The device kind has no row in ``CHIP_PEAKS``."""


def chip_peaks(device_kind: str) -> ChipPeaks:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise UnknownChipError(
            f"no published peaks recorded for device kind "
            f"{device_kind!r} (known: {sorted(CHIP_PEAKS)}); add a "
            "sourced row to utils/chips.py rather than reporting a "
            "share of another chip's peak") from None


def local_chip_peaks() -> ChipPeaks | None:
    """Peaks of the device this process runs on: ``None`` on a non-TPU
    backend (the CPU has no MFU to report), an error on a TPU kind the
    table does not know."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    return chip_peaks(dev.device_kind)
