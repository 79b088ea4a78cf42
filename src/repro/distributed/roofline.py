"""Three-term roofline model, with the peaks of the chip it targets.

  t_compute    = HLO_FLOPs  / (chips * peak FLOP/s)
  t_memory     = HLO_bytes  / (chips * HBM bytes/s)
  t_collective = coll_bytes / (chips * ICI bytes/s per link)

Peaks come from :data:`CHIP_PEAKS`, keyed by ``device_kind`` as JAX
reports it; a kind that is not in the table raises rather than borrowing
another chip's numbers.  Inputs come from the dry-run:
``compiled.cost_analysis()`` (flops, bytes accessed) and the HLO
collective parser.  MODEL_FLOPS = 6*N_active*D (dense: N_active = N)
gives the useful-compute ratio.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float        # bf16 FLOP/s per chip
    hbm_bw: float       # HBM bytes/s per chip
    ici_bw: float       # interconnect bytes/s per link
    hbm_bytes: float    # HBM capacity per chip


CHIP_PEAKS: Dict[str, ChipPeaks] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
    # at 819 GB/s, 1,600 Gbit/s interconnect per chip (4 links of 50 GB/s).
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                             hbm_bytes=16e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(CHIP_PEAKS)}") from None


@dataclasses.dataclass
class Roofline:
    flops: float
    bytes_accessed: float
    collective_bytes: float
    chips: int
    device_kind: str
    model_flops: Optional[float] = None   # 6 * N_active * tokens

    def __post_init__(self):
        chip_peaks(self.device_kind)   # an unknown chip fails here

    @property
    def peaks(self) -> ChipPeaks:
        return chip_peaks(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops / (self.chips * self.peaks.flops)

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / (self.chips * self.peaks.hbm_bw)

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / (self.chips * self.peaks.ici_bw)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> Optional[float]:
        if not self.model_flops or not self.flops:
            return None
        return self.model_flops / self.flops

    @property
    def mfu_bound(self) -> Optional[float]:
        """Upper bound on model-FLOPs utilisation implied by the terms:
        useful FLOPs / (chips * peak * bound_time)."""
        if not self.model_flops or self.bound_time == 0:
            return None
        return self.model_flops / (self.chips * self.peaks.flops
                                   * self.bound_time)

    def to_dict(self) -> Dict:
        return {
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "collective_bytes": self.collective_bytes,
            "chips": self.chips,
            "device_kind": self.device_kind,
            "model_flops": self.model_flops,
            "t_compute": self.t_compute,
            "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "dominant": self.dominant,
            "useful_flops_ratio": self.useful_flops_ratio,
            "mfu_bound": self.mfu_bound,
        }


def model_flops_train(n_params_active: float, tokens: float) -> float:
    """6*N*D for a train step (fwd 2ND + bwd 4ND)."""
    return 6.0 * n_params_active * tokens


def model_flops_forward(n_params_active: float, tokens: float) -> float:
    return 2.0 * n_params_active * tokens
