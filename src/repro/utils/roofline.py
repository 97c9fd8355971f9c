"""Three-term roofline model from compiled dry-run artifacts.

    compute term    = FLOPs_per_chip / peak_FLOPs
    memory term     = HBM bytes_per_chip / HBM_bw
    collective term = collective bytes_per_chip / ICI_bw

Peaks come from one table keyed by ``device_kind`` (:data:`PEAKS`); a kind
that is not in it raises.  ``cost_analysis()`` on a partitioned module
reports *per-device* flops/bytes, so no division by chip count is needed;
collective bytes come from the HLO parser (also per-device, GSPMD emits the
per-shard module).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Optional

from repro.utils import hlo as hlo_mod

__all__ = ["HW", "PEAKS", "TPU_V5E", "peaks", "RooflineTerms", "analyze",
           "model_flops"]


@dataclasses.dataclass(frozen=True)
class HW:
    name: str
    peak_flops: float          # per chip, bf16
    hbm_bw: float              # bytes/s per chip
    ici_bw: float              # bytes/s of chip-to-chip interconnect per chip


#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
#: TPU v5e — Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
#: 16 GB HBM at 819 GB/s, 1,600 Gbit/s (200 GB/s) inter-chip interconnect.
PEAKS: dict[str, HW] = {
    "TPU v5 lite": HW(name="tpu-v5e", peak_flops=197e12, hbm_bw=819e9,
                      ici_bw=1600e9 / 8),
}

#: The target chip of the dry-run and cost-model predictions.
TPU_V5E = PEAKS["TPU v5 lite"]


def peaks(device_kind: str) -> HW:
    """The published peaks of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


@dataclasses.dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    flops_per_chip: float
    hbm_bytes_per_chip: float
    coll_bytes_per_chip: float
    coll_breakdown: dict[str, int]
    t_compute: float
    t_memory: float
    t_collective: float
    model_flops_total: float            # 6·N·D (MoE: active N)
    peak_flops: float                   # the chip's bf16 peak (PEAKS)
    useful_ratio: float                 # model_flops_per_chip / hlo_flops
    argument_bytes: int = 0
    temp_bytes: int = 0
    output_bytes: int = 0

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Perfect-overlap lower bound: max of the three terms."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of ideal compute roofline this step achieves, assuming
        perfect overlap: t_compute / max(all terms) — 1.0 means compute-bound
        with zero exposed memory/collective time."""
        return self.t_compute / max(self.step_time, 1e-30)

    @property
    def mfu_bound(self) -> float:
        """Upper bound on model-flops-utilisation: useful flops over peak at
        the step-time lower bound."""
        useful = self.flops_per_chip * self.useful_ratio
        return useful / (self.step_time * self.peak_flops) \
            if self.step_time else 0.0

    def to_json(self) -> dict[str, Any]:
        d = dataclasses.asdict(self)
        d["dominant"] = self.dominant
        d["step_time"] = self.step_time
        d["roofline_fraction"] = self.roofline_fraction
        d["mfu_bound"] = self.mfu_bound
        return d


def model_flops(cfg, n_tokens: int, *, training: bool = True) -> float:
    """6·N·D rule (fwd 2ND + bwd 4ND); serving fwd-only = 2·N·D."""
    n = cfg.active_param_count()
    return (6.0 if training else 2.0) * n * n_tokens


def analyze(compiled, *, arch: str, shape: str, mesh_name: str,
            n_chips: int, cfg=None, n_tokens: int = 0,
            training: bool = True, hw: HW = TPU_V5E,
            hlo_text: Optional[str] = None) -> RooflineTerms:
    """Build the three roofline terms from one compiled executable."""
    ca = compiled.cost_analysis()
    if not isinstance(ca, dict):            # some jax versions: list of dicts
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    hbm_bytes = float(ca.get("bytes accessed", 0.0))
    txt = hlo_text if hlo_text is not None else compiled.as_text()
    coll = hlo_mod.collective_bytes(txt)

    mf = model_flops(cfg, n_tokens, training=training) if cfg else 0.0
    mf_per_chip = mf / max(n_chips, 1)
    useful = (mf_per_chip / flops) if flops else 0.0

    ma = None
    try:
        ma = compiled.memory_analysis()
    except Exception:
        pass

    return RooflineTerms(
        arch=arch, shape=shape, mesh=mesh_name,
        flops_per_chip=flops,
        hbm_bytes_per_chip=hbm_bytes,
        coll_bytes_per_chip=float(coll.get("total", 0)),
        coll_breakdown={k: v for k, v in coll.items() if k != "total"},
        t_compute=flops / hw.peak_flops,
        t_memory=hbm_bytes / hw.hbm_bw,
        t_collective=coll.get("total", 0) / hw.ici_bw,
        model_flops_total=mf,
        peak_flops=hw.peak_flops,
        useful_ratio=useful,
        argument_bytes=getattr(ma, "argument_size_in_bytes", 0) if ma else 0,
        temp_bytes=getattr(ma, "temp_size_in_bytes", 0) if ma else 0,
        output_bytes=getattr(ma, "output_size_in_bytes", 0) if ma else 0,
    )


def save_jsonl(path: str, terms: list[RooflineTerms]) -> None:
    with open(path, "w") as f:
        for t in terms:
            f.write(json.dumps(t.to_json()) + "\n")


def load_jsonl(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(l) for l in f if l.strip()]
