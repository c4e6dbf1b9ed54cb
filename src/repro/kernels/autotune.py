"""Roofline-steered autotuner for the CSR kernel stack (ROADMAP item 6).

One relation's fixpoint cost is set by knobs the engine can only guess at
statically: the sliced-ELL capacity ladder (``core.sparse`` ``ell_cfg`` —
how padding tracks the in-degree distribution), the Pallas block sizes
(``chunk``/``bn``) and whether the tile-skipping kernel beats the jnp
segment path at all on the current backend.  The Wisconsin study
(arXiv 1812.03975) finding — layout/tuning choices dominate in-memory
Datalog once the algorithmic wins are in — is why this is a *measured*
search, not a formula:

1. **Seed analytically.**  Every candidate's allocated segment slots
   (``e_alloc``) follow from the in-degree histogram alone — no build
   needed — and the roofline model (``obs.roofline_attr``) turns that into
   a predicted per-iteration lower bound.  Candidates rank by prediction;
   only the top few get timed (the search is O(histogram), the timing is
   the expensive part).
2. **Measure the shortlist.**  Each finalist builds its layout and runs the
   real batched fixpoint (``fixpoint_csr_cached`` — compile cost excluded
   by a warmup run) on a seed batch.
3. **Score by achieved-vs-peak.**  The score is the roofline fraction of
   *useful* work (2·B·|E| semiring ops against live arcs) — maximizing it
   is minimizing wall time, but the number is comparable across layouts;
   ``explain()["kernels"]["tuning"]`` reports it per tuned predicate.

Results cache per (graph-shape, kind) signature and launch width —
degree-profile buckets, not exact graphs — so a serving tier rebuilding a
relation after a tail fold reuses the tuned config unless the shape class
actually moved.
Pallas-kernel candidates (``use_kernel=True``) are timed only on a TPU
backend: under ``interpret=True`` the kernels are emulation, and timing
emulation would steer the tuner off a cliff.  A kernel candidate the kernel's
own VMEM arithmetic (``kernels.spmv.vmem_bytes``) refuses at this width is
left out too; either way the candidate stays in ``TuneResult.candidates``
with ``measured_s=None`` and the reason in ``skipped``.  Pin a config
(``DatalogService(tune=KernelConfig(...))``) to skip measurement entirely.

Peak-relative figures (``predicted_s``, ``frac_peak_*``) use the running
device's peaks by ``device_kind`` and are ``None`` on a kind without an
entry, the CPU among them.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import numpy as np

from ..core import sparse as _sparse
from ..core.seminaive import quantize_ladder, quantize_rows
from ..obs.roofline_attr import (achieved_fractions, csr_launch_cost,
                                 predicted_seconds)
from ..roofline.report import default_peaks
from .spmv import VMEM_LIMIT_BYTES, vmem_bytes

__all__ = ["KernelConfig", "TuneResult", "autotune", "build_tuned",
           "graph_signature", "clear_cache", "DEFAULT_SLICE_CANDIDATES"]


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One point in the tuning space.  Frozen + hashable: usable as a
    ``PlanOptions`` field and as a pinned config."""

    slice_floor: int = 1  # sliced-ELL ladder floor (ell_cfg[0])
    slice_stride: int = 1  # ladder stride; 0 = single-width legacy ELL
    chunk: int = 128  # Pallas edge-chunk block
    bn: int = 128  # Pallas column-tile block
    use_kernel: bool = False  # route the Pallas SpMV (with tile-skip plan)

    @property
    def ell_cfg(self) -> tuple:
        return (self.slice_floor, self.slice_stride)

    @property
    def kernel_plan(self) -> tuple | None:
        return (self.chunk, self.bn) if self.use_kernel else None

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


#: the legacy layout — the measured baseline every gain is relative to
SINGLE_WIDTH = KernelConfig(slice_floor=1, slice_stride=0)

#: slice ladders worth trying: pure power-of-two classes, coarser strides
#: (fewer slices, more within-slice pad), higher floors (fewer tiny slices)
DEFAULT_SLICE_CANDIDATES = ((1, 1), (2, 1), (8, 1), (4, 2), (1, 0))

#: Pallas block sizes tried when kernel candidates are in scope
DEFAULT_BLOCK_CANDIDATES = ((128, 128), (256, 128), (128, 256))


@dataclasses.dataclass
class TuneResult:
    config: KernelConfig
    gain: float  # baseline_seconds / best_seconds (>= 1 when tuning won)
    baseline_seconds: float
    best_seconds: float
    frac_peak_flops: Optional[float]  # achieved fraction of peak for
    frac_peak_bw: Optional[float]  # USEFUL work; None without peaks
    signature: tuple
    #: [{config, predicted_s, measured_s | None, skipped | None}, ...]
    candidates: list
    cached: bool = False

    def as_dict(self) -> dict:
        return {"config": self.config.as_dict(), "gain": self.gain,
                "baseline_seconds": self.baseline_seconds,
                "best_seconds": self.best_seconds,
                "frac_peak_flops": self.frac_peak_flops,
                "frac_peak_bw": self.frac_peak_bw,
                "signature": list(self.signature), "cached": self.cached,
                "candidates": [
                    {"config": c["config"].as_dict(),
                     "predicted_s": c["predicted_s"],
                     "measured_s": c["measured_s"],
                     "skipped": c.get("skipped")}
                    for c in self.candidates]}


_CACHE: dict[tuple, TuneResult] = {}


def clear_cache() -> None:
    _CACHE.clear()


def build_tuned(edges: np.ndarray, n_alloc: int, kind: str,
                cfg: KernelConfig, tail_min: int = 8) -> "_sparse.CSRMatrix":
    """``build_csr`` with a config's layout + kernel plan applied."""
    return _sparse.build_csr(edges, n_alloc, kind, tail_min=tail_min,
                             ell_cfg=cfg.ell_cfg,
                             kernel_plan=cfg.kernel_plan)


def _indegree(edges: np.ndarray, n_alloc: int) -> np.ndarray:
    if len(edges) == 0:
        return np.zeros(n_alloc, np.int64)
    return np.bincount(edges[:, 1].astype(np.int64), minlength=n_alloc)


def graph_signature(edges: np.ndarray, n_alloc: int, kind: str) -> tuple:
    """The tuning-cache key: a degree-profile shape class, not the graph.

    Buckets: edge-count bucket (the CSR capacity bucket), max-in-degree
    bucket, and a heavy-tail flag (max > 8x mean — the regime where slicing
    matters).  Graphs sharing the class share the tuned config; a tail fold
    that keeps the class warm-hits the cache.
    """
    m = len(edges)
    indeg = _indegree(edges, n_alloc)
    max_d = int(indeg.max()) if m else 0
    mean_d = m / max(int((indeg > 0).sum()), 1)
    heavy = max_d > 8 * max(mean_d, 1.0)
    return (kind, n_alloc, quantize_rows(m + 1),
            quantize_rows(max_d, minimum=1), bool(heavy))


def _predicted_e_alloc(indeg: np.ndarray, ell_cfg: tuple) -> int:
    """A candidate ladder's allocated spine slots, from the histogram alone
    (mirrors ``core.sparse._sliced_ell_index`` without building tables)."""
    floor, stride = ell_cfg
    live = indeg[indeg > 0]
    max_d = int(live.max()) if len(live) else 0
    caps = np.asarray(quantize_ladder(floor, stride, max_d), np.int64)
    if not len(live):
        return int(caps[0])
    which = np.searchsorted(caps, live, side="left")
    counts = np.bincount(which, minlength=len(caps))
    counts[0] += 1  # the shared sentinel row
    return int((counts * caps).sum())


def _measure_fixpoint(csr, srcs, spmv, repeats: int = 3) -> float:
    """Median steady-state seconds of one batched fixpoint (warmup excluded
    — compile cost is amortized across a serving relation's lifetime)."""
    init = _sparse.rows_from_sources(csr, srcs)
    jax.block_until_ready(
        _sparse.fixpoint_csr_cached(csr, init, spmv=spmv).table)
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(
            _sparse.fixpoint_csr_cached(csr, init, spmv=spmv).table)
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def autotune(edges: np.ndarray, n_alloc: int, kind: str, *, batch: int = 8,
             top_k: int = 2, include_kernels: Optional[bool] = None,
             slice_candidates: tuple = DEFAULT_SLICE_CANDIDATES,
             block_candidates: tuple = DEFAULT_BLOCK_CANDIDATES,
             hw=None, use_cache: bool = True) -> TuneResult:
    """Pick a :class:`KernelConfig` for one relation by measured search.

    ``include_kernels=None`` times Pallas candidates on a TPU backend only
    (interpret-mode timings are meaningless) and lists them as skipped
    elsewhere; ``False`` leaves them out.  ``batch`` is the widest batch the
    relation will be launched at: the finalists are timed at that width,
    and a kernel candidate whose VMEM at that width is over the scoped limit
    is skipped.  ``hw`` overrides the device's peaks.
    """
    edges = np.asarray(edges, np.int64).reshape(-1, 2 if kind == "bool" else 3)
    sig = graph_signature(edges, n_alloc, kind)
    B = max(batch, 1)
    if use_cache and (sig, B) in _CACHE:
        return dataclasses.replace(_CACHE[sig, B], cached=True)
    kernel_skip = None
    if include_kernels is None:
        include_kernels, backend = True, jax.default_backend()
        if backend != "tpu":
            kernel_skip = (f"backend {backend}: interpret-mode kernel "
                           "timings are emulation, not a device measurement")
    if hw is None:
        hw = default_peaks()
    indeg = _indegree(edges, n_alloc)
    m = len(edges)
    itemsize = 1 if kind == "bool" else 4

    # -- 1. analytic seed: rank every layout by its allocated segment slots
    # (every term of the roofline cost grows with them, so this is the
    # roofline ranking on any chip) ------------------------------------------
    ranked = []
    for ell_cfg in slice_candidates:
        e_alloc = _predicted_e_alloc(indeg, ell_cfg)
        base = KernelConfig(slice_floor=ell_cfg[0], slice_stride=ell_cfg[1])
        ranked.append((e_alloc, base))
    ranked.sort(key=lambda t: t[0])
    shortlist = [cfg for _, cfg in ranked[:top_k]]
    if SINGLE_WIDTH not in shortlist:
        shortlist.append(SINGLE_WIDTH)  # the gain denominator always runs
    skipped: dict = {}
    if include_kernels:
        for c, b in block_candidates:
            cfg = dataclasses.replace(shortlist[0], use_kernel=True,
                                      chunk=c, bn=b)
            need = vmem_bytes(kind, B, n_alloc, chunk=c, bn=b)
            if need > VMEM_LIMIT_BYTES:  # refused on any backend
                skipped[cfg] = (f"needs {need} bytes of VMEM at ({B}, "
                                f"{n_alloc}), over the {VMEM_LIMIT_BYTES}-"
                                "byte scoped limit")
            elif kernel_skip is not None:
                skipped[cfg] = kernel_skip
            else:
                shortlist.append(cfg)
    e_allocs = {cfg.ell_cfg: e for e, cfg in ranked}
    e_allocs.setdefault(SINGLE_WIDTH.ell_cfg,
                        _predicted_e_alloc(indeg, SINGLE_WIDTH.ell_cfg))

    def predicted(cfg):
        return predicted_seconds(csr_launch_cost(
            B, n_alloc, e_allocs[cfg.ell_cfg], itemsize, iters=1), hw)

    # -- 2./3. measure the shortlist, score by useful-work roofline fraction
    from . import ops as _kops  # local import: kernels.ops pulls every kernel
    srcs = (np.arange(B) % max(n_alloc, 1)).astype(np.int64)
    useful = csr_launch_cost(B, n_alloc, max(m, 1), itemsize, iters=1)
    rows = []
    for cfg in shortlist:
        csr = build_tuned(edges, n_alloc, kind, cfg)
        spmv = _kops.csr_frontier_step(kind) if cfg.use_kernel else None
        secs = _measure_fixpoint(csr, srcs, spmv)
        rows.append({"config": cfg, "measured_s": secs,
                     "predicted_s": predicted(cfg), "skipped": None})
    for cfg, why in skipped.items():
        rows.append({"config": cfg, "measured_s": None,
                     "predicted_s": predicted(cfg), "skipped": why})
    for _, cfg in ranked[top_k:]:  # report the pruned tail too
        if all(r["config"] != cfg for r in rows):
            rows.append({"config": cfg, "measured_s": None,
                         "predicted_s": predicted(cfg),
                         "skipped": "pruned by the analytic seed"})
    measured = [r for r in rows if r["measured_s"] is not None]
    best = min(measured, key=lambda r: r["measured_s"])
    baseline = next(r for r in measured if r["config"] == SINGLE_WIDTH)
    fr = achieved_fractions(useful, best["measured_s"], hw)
    res = TuneResult(
        config=best["config"],
        gain=baseline["measured_s"] / max(best["measured_s"], 1e-12),
        baseline_seconds=baseline["measured_s"],
        best_seconds=best["measured_s"],
        frac_peak_flops=fr["frac_peak_flops"],
        frac_peak_bw=fr["frac_peak_bw"],
        signature=sig, candidates=rows)
    if use_cache:
        _CACHE[sig, B] = res
    return res
