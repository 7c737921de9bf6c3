"""Roofline-term computation from compiled dry-run artifacts.

  compute term    = HLO_FLOPs / (chips x peak FLOP/s)
  memory term     = HLO_bytes / (chips x HBM bytes/s)
  collective term = collective_bytes / (chips x ICI bytes/s/link)
                    (cross-pod replica groups priced at DCN bandwidth)

Peaks come from ``PEAKS``, keyed by ``device_kind``; a kind that is not in
the table is an error, never a default.

``compiled.cost_analysis()`` counts while-loop bodies once, which
undercounts scan-over-layers models by ~n_layers; the loop-aware HLO
analyzer (launch/hlo_analysis.py) recovers trip counts from loop
conditions and scales every term.  Raw cost_analysis numbers are kept in
the record for reference.  All per-device quantities come from the
partitioned (per-device) module, so dividing by per-chip peaks directly is
the same as the total/(chips x peak) formulation.
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.launch.hlo_analysis import HLOCost, analyze


@dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float                  # FLOP/s
    hbm_bw: float                      # B/s
    ici_bw: float                      # B/s per link
    dcn_bw: float                      # B/s per chip cross-pod
    source: str


PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
    # 1,600 Gbit/s ICI per chip (4 links -> 50 GB/s each).  The DCN figure
    # (50 Gbit/s per chip) is an assumption, not a published peak.
    "TPU v5 lite": ChipPeaks(197e12, 819e9, 50e9, 6.25e9,
                             'Google Cloud docs "TPU v5e"; DCN assumed'),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no roofline peaks for device_kind "
                         f"{device_kind!r}; have {sorted(PEAKS)}") from None


@dataclass
class Roofline:
    cost: HLOCost                      # loop-aware, per device
    n_devices: int
    peaks: ChipPeaks
    model_flops_total: float = 0.0
    raw_flops: float = 0.0             # cost_analysis (loop-unaware)
    raw_bytes: float = 0.0

    @property
    def compute_s(self) -> float:
        return self.cost.flops / self.peaks.flops_bf16

    @property
    def memory_s(self) -> float:
        return self.cost.hbm_bytes / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        ici = ((self.cost.coll_bytes - self.cost.coll_cross_pod_bytes)
               / self.peaks.ici_bw)
        dcn = self.cost.coll_cross_pod_bytes / self.peaks.dcn_bw
        return ici + dcn

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — remat/redundancy waste (can exceed 1
        only if the analyzer under-counts)."""
        total = self.cost.flops * self.n_devices
        return self.model_flops_total / total if total else 0.0

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """useful_compute_time / bound_time: the fraction of the ideal
        (model-FLOPs-only) roofline this step achieves if it runs at its
        dominant-term speed."""
        useful_s = ((self.model_flops_total / self.n_devices)
                    / self.peaks.flops_bf16)
        return useful_s / self.bound_s if self.bound_s else 0.0

    def to_dict(self) -> dict:
        return {
            "flops_per_dev": self.cost.flops,
            "hbm_bytes_per_dev": self.cost.hbm_bytes,
            "collective_bytes": self.cost.coll_bytes,
            "collective_cross_pod_bytes": self.cost.coll_cross_pod_bytes,
            "collective_per_op": self.cost.coll_per_op,
            "collective_counts": self.cost.coll_counts,
            "hbm_per_op": {k: round(v) for k, v in self.cost.hbm_per_op.items()},
            "while_trip_counts": self.cost.while_trips,
            "raw_cost_analysis_flops": self.raw_flops,
            "raw_cost_analysis_bytes": self.raw_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "model_flops_total": self.model_flops_total,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def model_flops(n_params_active: int, n_tokens: int, kind: str) -> float:
    """6·N·D for training, 2·N·D for inference forward."""
    if kind == "train":
        return 6.0 * n_params_active * n_tokens
    return 2.0 * n_params_active * n_tokens


def build_roofline(compiled, n_devices: int, model_flops_total: float,
                   device_kind: str, pod_size: int = 256) -> Roofline:
    """``device_kind`` names the chip the program is priced for (the
    dry-run compiles for placeholder host devices but models a pod of
    real chips)."""
    peaks = peaks_for(device_kind)
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    raw_flops = float(ca.get("flops", 0.0))
    raw_bytes = float(ca.get("bytes accessed", 0.0))
    cost = analyze(compiled.as_text(), n_devices, pod_size)
    return Roofline(cost, n_devices, peaks, model_flops_total, raw_flops,
                    raw_bytes)
