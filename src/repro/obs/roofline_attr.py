"""Analytic roofline cost of fixpoint launches, for the kernel autotuner.

``kernels.autotune`` prices each candidate layout with the flop/byte model
below and reports its measured time against the running device's peaks
(:class:`repro.roofline.report.HW`, keyed by ``device_kind``); on a kind
with no entry (the CPU among them) every peak-relative figure is ``None``:
not measured.

Analytic cost models (per fixpoint *iteration*; B = padded batch rows,
n = padded domain, e = allocated packed-arc slots incl. ELL padding):

- ``frontier_matmul`` (dense vector form): one (B,n)x(n,n) ⊕.⊗ product
  → ``2·B·n²`` flops; bytes = arc matrix + frontier read + write.
- ``csr_spmv`` (segment step): gather + segment-⊕ over packed arcs
  → ``2·B·e`` flops; bytes = arc arrays (src/val/ell) + frontier traffic.

These are *model* flops (useful work at the semiring level), the same
convention as ``roofline.model_flops`` — achieved fractions below 1e-2
on the dense path are the expected signature of masked-out converged
rows, not a measurement bug.
"""
from __future__ import annotations

from typing import Dict, Optional

from ..roofline.report import HW

__all__ = ["dense_launch_cost", "csr_launch_cost", "predicted_seconds",
           "achieved_fractions"]


def dense_launch_cost(B: int, n: int, itemsize: int, iters: int
                      ) -> Dict[str, float]:
    """Flops/bytes for a dense vector-form fixpoint: ``iters`` (B,n)x(n,n)
    semiring products against a resident arc matrix."""
    flops_per_iter = 2.0 * B * n * n
    bytes_per_iter = itemsize * (n * n + 2.0 * B * n)  # arc + read + write
    return {"flops": flops_per_iter * iters, "bytes": bytes_per_iter * iters}


def csr_launch_cost(B: int, n_alloc: int, e_alloc: int, itemsize: int,
                    iters: int) -> Dict[str, float]:
    """Flops/bytes for a CSR segment-step fixpoint: ``iters`` gather +
    segment-⊕ passes over ``e_alloc`` packed arc slots (ELL + COO tail)."""
    flops_per_iter = 2.0 * B * e_alloc
    bytes_per_iter = (
        e_alloc * (4 + itemsize + 4)        # src_idx + edge_val + ell_idx
        + itemsize * 2.0 * B * n_alloc      # frontier read + write
        + itemsize * B * e_alloc            # gathered contributions
    )
    return {"flops": flops_per_iter * iters, "bytes": bytes_per_iter * iters}


def predicted_seconds(cost: Dict[str, float],
                      hw: Optional[HW]) -> Optional[float]:
    """Roofline lower bound for an analytic cost: the slower of its compute
    and memory terms (``None`` without peaks).  The autotuner
    (``kernels.autotune``) reports it beside each candidate's timing."""
    if hw is None:
        return None
    return max(cost["flops"] / hw.peak_flops, cost["bytes"] / hw.hbm_bw)


def achieved_fractions(cost: Dict[str, float], seconds: float,
                       hw: Optional[HW]) -> Dict[str, Optional[float]]:
    """Achieved-vs-peak fractions for a measured run of an analytic cost —
    the autotuner's report (``cost`` holds *useful* work, so a layout that
    shrinks padding raises the fraction at equal wall time).  ``None``
    fractions without peaks."""
    if hw is None:
        return {"frac_peak_flops": None, "frac_peak_bw": None}
    secs = max(seconds, 1e-12)
    return {"frac_peak_flops": cost["flops"] / secs / hw.peak_flops,
            "frac_peak_bw": cost["bytes"] / secs / hw.hbm_bw}
