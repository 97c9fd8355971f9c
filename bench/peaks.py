"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e -- Google Cloud documentation, "TPU v5e" (system architecture
table): 197 TFLOP/s bf16 and 16 GB of HBM2 at 819 GB/s.  The FLOP peak is
the MXU's bf16 rate, the only one published; the f32 vector work of the cells
here is bounded far below it by HBM, so the FLOP term of a least time is a
lower bound that never binds.  A kind that is not in the table is an
error, not a default.
"""
from __future__ import annotations

import dataclasses

__all__ = ["Peaks", "PEAKS", "lookup", "least_seconds"]


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float


PEAKS = {
    "TPU v5 lite": Peaks(flops_per_s=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16e9),
}


def lookup(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None


def least_seconds(work, peaks: Peaks) -> float:
    """The least time the chip could take for ``work``: the larger of its
    FLOPs and its HBM bytes over their peaks."""
    return max(work.flops / peaks.flops_per_s,
               work.hbm_bytes / peaks.hbm_bytes_per_s)
