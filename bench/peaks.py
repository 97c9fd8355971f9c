"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e -- Google Cloud documentation, "TPU v5e" (system architecture
table): 197 TFLOP/s bf16 and 16 GB of HBM2 at 819 GB/s.  The FLOP peak is
the MXU's bf16 rate, the only one published; the f32 vector work of the cells
here is bounded far below it by HBM, so the FLOP term of a least time is a
lower bound that never binds.  Its on-chip memory: 128 MiB of VMEM, the
``"TPU v5 lite"`` entry of JAX 0.9.0's
``jax.experimental.pallas.tpu.get_tpu_info()`` table.  A kind that is not in
the table is an error, not a default; so is a least time that needs a VMEM
size the table does not give.

A least time lets the chip keep n-vectors in VMEM: those a work count lists
(``Work.vectors``), most accessed first, as many as fit in
:data:`RESIDENT_SHARE` of VMEM, the rest being left to the streams.  Their
accesses move no HBM bytes.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

__all__ = ["Peaks", "PEAKS", "RESIDENT_SHARE", "lookup", "resident_bytes",
           "least_seconds"]

#: the share of VMEM a least time may fill with resident vectors
RESIDENT_SHARE = 0.75


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    vmem_bytes: Optional[float] = None


PEAKS = {
    "TPU v5 lite": Peaks(flops_per_s=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16e9, vmem_bytes=128 * 2 ** 20),
}


def lookup(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None


def resident_bytes(work, peaks: Peaks) -> float:
    """The HBM bytes of ``work`` that the chip keeps on chip: the accesses
    of its vectors, most accessed first, of those that fit in
    ``RESIDENT_SHARE`` of VMEM."""
    if not work.vectors:
        return 0.0
    if peaks.vmem_bytes is None:
        raise ValueError(f"no published VMEM size in {peaks}: the least "
                         f"time of work with vectors needs one")
    room, kept = RESIDENT_SHARE * peaks.vmem_bytes, 0.0
    for size, accesses in sorted(work.vectors, key=lambda v: -v[1]):
        if size <= room:
            room -= size
            kept += size * accesses
    return kept


def least_seconds(work, peaks: Peaks) -> float:
    """The least time the chip could take for ``work``: the larger of its
    FLOPs and its HBM bytes, less those it keeps on chip, over their
    peaks."""
    return max(work.flops / peaks.flops_per_s,
               (work.hbm_bytes - resident_bytes(work, peaks))
               / peaks.hbm_bytes_per_s)
