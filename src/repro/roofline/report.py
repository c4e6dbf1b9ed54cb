"""Three-term roofline from the compiled dry-run artifact.

    compute    = HLO_FLOPs_per_device / peak_FLOP/s
    memory     = HLO_bytes_per_device / HBM_bw
    collective = collective_operand_bytes_per_device / link_bw

(``cost_analysis()`` on a SPMD-partitioned executable reports per-device
numbers, so no further division by chip count is applied; collective bytes
come from the per-device HLO module for the same reason.)

Hardware model: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses

from .hlo import parse_collectives


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12  # bf16
    hbm_bw: float = 819e9
    link_bw: float = 50e9
    hbm_bytes: float = 16e9


V5E = HW()

#: peaks by ``jax.Device.device_kind``.  TPU v5e ("TPU v5 lite"): Google
#: Cloud's "TPU v5e" page — 197 TFLOP/s bf16, 819 GB/s and 16 GB of HBM.
PEAKS = {"TPU v5 lite": V5E}


def peaks_for(device_kind: str) -> HW | None:
    """The peaks of one chip kind, or None for a kind with no entry (the
    CPU among them): a fraction of peak there is "not measured", never a
    fraction of some other chip's peak."""
    return PEAKS.get(device_kind)


def default_peaks() -> HW | None:
    """:func:`peaks_for` the device JAX places arrays on by default."""
    import jax
    return peaks_for(jax.devices()[0].device_kind)


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    coll_detail: dict
    model_flops: float
    useful_ratio: float  # MODEL_FLOPS / (HLO_FLOPs × chips)
    dominant: str

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def model_flops(cfg, shape, n_active_params: int, train: bool) -> float:
    """6·N·D (dense/active) per step; decode steps use D = batch tokens."""
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        return 6.0 * n_active_params * tokens
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return 2.0 * n_active_params * tokens
    # decode: one token per sequence
    return 2.0 * n_active_params * shape.global_batch


def roofline(cost: dict, hlo_text: str, n_chips: int, mflops: float,
             hw: HW = V5E) -> RooflineTerms:
    """Prefers the trip-count-aware HLO walker (XLA's cost_analysis counts
    loop bodies once — see walker.py); raw cost numbers are kept by the
    caller for reference."""
    from .walker import walk_costs

    w = walk_costs(hlo_text)
    flops = float(w.flops)
    byts = float(w.bytes)
    compute_s = flops / hw.peak_flops
    memory_s = byts / hw.hbm_bw
    collective_s = w.coll_bytes / hw.link_bw
    useful = mflops / max(flops * n_chips, 1.0)
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    return RooflineTerms(
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        flops_per_device=flops, bytes_per_device=byts,
        coll_bytes_per_device=float(w.coll_bytes),
        coll_detail={"bytes": w.coll_by_kind, "dynamic_loops": w.dynamic_loops},
        model_flops=mflops, useful_ratio=useful, dominant=dominant,
    )
