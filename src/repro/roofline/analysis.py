"""Roofline terms from a compiled dry-run artifact (TPU v5e targets).

    compute term    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory term     = HLO_bytes / (chips * HBM_bw)
    collective term = collective_bytes / (chips * link_bw)

HLO_FLOPs/bytes come from the scan-corrected HLO parse (repro.roofline.hlo;
``cost_analysis()`` under-counts while bodies) and are per-device — chips
cancel, so terms are computed from per-device numbers directly. MODEL_FLOPS
= 6·N·D (dense) / 6·N_active·D (MoE) per the brief; the ratio
MODEL_FLOPS / HLO_FLOPs measures how much compiled compute is "useful"
(catches remat and masked-block waste).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from repro.configs.base import ArchConfig, InputShape
from repro.roofline.hlo import HloStats


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float      # bf16 FLOP/s per chip
    hbm_bw: float     # HBM bytes/s per chip
    ici_bw: float     # inter-chip bytes/s per link


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# "TPU v5 lite" is the TPU v5e — Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
# interconnect over 4 links (50 GB/s per link).
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}
# the chip the dry-run roofline is projected onto
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; a kind not in the table is
    an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add "
            f"them to roofline.analysis.PEAKS with their source") from None


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    hlo_flops_per_device: float
    useful_ratio: float
    collectives: Dict[str, float]
    per_device_hbm_bytes: float

    def as_dict(self):
        return dataclasses.asdict(self)


def model_flops(cfg: ArchConfig, shape: InputShape,
                n_active: Optional[float] = None) -> float:
    """6·N·D with N = active params; D = processed tokens.

    train: fwd+bwd = 6·N·D; prefill: 2·N·D; decode: 2·N per token·B.
    n_active, when given, is the exact count from the instantiated params
    tree (minus inactive experts); else the config estimate."""
    if n_active is None:
        n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # one decode step


def compute_roofline(cfg: ArchConfig, shape: InputShape, stats: HloStats,
                     n_chips: int, *, param_bytes_per_device: float = 0.0,
                     n_active: Optional[float] = None) -> Roofline:
    flops_dev = stats.dot_flops
    # memory: dot operand traffic is the dominant HBM term; add param reads
    # once (weights streamed from HBM each step even when dots fuse)
    mem_bytes_dev = max(stats.dot_bytes, param_bytes_per_device)
    chip = peaks(TARGET_KIND)
    compute_s = flops_dev / chip.flops
    memory_s = mem_bytes_dev / chip.hbm_bw
    coll_s = stats.collective_bytes / chip.ici_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": coll_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape, n_active)
    hlo_total = flops_dev * n_chips
    return Roofline(
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=coll_s,
        dominant=dominant,
        model_flops=mf,
        hlo_flops_per_device=flops_dev,
        useful_ratio=mf / hlo_total if hlo_total else 0.0,
        collectives=dict(stats.collectives),
        per_device_hbm_bytes=mem_bytes_dev,
    )
