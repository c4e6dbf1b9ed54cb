"""Roofline attribution for serving-path kernel launches.

ROADMAP item 6 (sliced-ELL, prefetch maps, autotuning) needs each kernel
change to be *attributable*: did the SpMV get closer to the memory roof,
or is the frontier matmul still compute-bound? This module hooks the
serving layer's fixpoint launches to the seed ``roofline/`` hardware
model (:class:`repro.roofline.report.HW`): every launch records an
analytic flop/byte model for its kernel plus the measured
launch→device-sync wall time, and ``report()`` emits achieved-vs-peak
fractions and the dominant roofline term per kernel.  The peaks are the
running device's, keyed by ``device_kind``
(:func:`repro.roofline.report.peaks_for`); on a kind with no entry (the CPU
among them) every peak-relative figure is ``None``: not measured.

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

import dataclasses
import threading
from typing import Any, Dict, Optional

from ..roofline.report import HW, default_peaks

__all__ = ["KernelAttribution", "dense_launch_cost", "csr_launch_cost",
           "predicted_seconds", "achieved_fractions"]


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


@dataclasses.dataclass
class _KernelTally:
    launches: int = 0
    iterations: int = 0
    seconds: float = 0.0
    flops: float = 0.0
    bytes: float = 0.0


class KernelAttribution:
    """Thread-safe accumulator of per-kernel launch costs + timings."""

    def __init__(self, hw: Optional[HW] = None):
        self.hw = hw  # None: the running device's peaks, read at report()
        self._lock = threading.Lock()
        self._tallies: Dict[str, _KernelTally] = {}

    def record(self, kernel: str, *, seconds: float, iterations: int,
               flops: float, bytes: float) -> None:
        """One launch: analytic cost + measured launch→sync wall time."""
        with self._lock:
            t = self._tallies.get(kernel)
            if t is None:
                t = self._tallies[kernel] = _KernelTally()
            t.launches += 1
            t.iterations += iterations
            t.seconds += seconds
            t.flops += flops
            t.bytes += bytes

    def report(self) -> Dict[str, Dict[str, Any]]:
        """Per-kernel achieved-vs-peak summary for ``explain()``; the
        peak-relative keys are ``None`` on a device without peaks."""
        with self._lock:
            tallies = {k: dataclasses.replace(t)
                       for k, t in self._tallies.items()}
        hw = self.hw if self.hw is not None else default_peaks()
        out: Dict[str, Dict[str, Any]] = {}
        for name, t in sorted(tallies.items()):
            secs = max(t.seconds, 1e-12)
            ach_flops = t.flops / secs
            ach_bw = t.bytes / secs
            row = {
                "launches": t.launches,
                "iterations": t.iterations,
                "seconds": t.seconds,
                "model_flops": t.flops,
                "model_bytes": t.bytes,
                "achieved_flops_per_s": ach_flops,
                "achieved_bytes_per_s": ach_bw,
                "frac_peak_flops": None,
                "frac_peak_bw": None,
                "dominant": None,
            }
            if hw is not None:
                compute_s = t.flops / hw.peak_flops
                memory_s = t.bytes / hw.hbm_bw
                row.update(
                    frac_peak_flops=ach_flops / hw.peak_flops,
                    frac_peak_bw=ach_bw / hw.hbm_bw,
                    dominant="compute" if compute_s >= memory_s
                    else "memory")
            out[name] = row
        return out

    def clear(self) -> None:
        with self._lock:
            self._tallies.clear()
