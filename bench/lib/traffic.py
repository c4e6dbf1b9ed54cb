"""The one general traffic generator: a mix file of parameters in, an
open-loop schedule of (due time, source vertex) out.

A mix (``bench/traffic/<mix>.json``) holds::

    {"arrivals": {"process": "poisson", "rate_qps": 12.0, "gap_seed": 1},
     "sources": {"dist": "uniform"} | {"dist": "zipf", "a": 1.2},
     "preroll_s": 5.0, "drain_s": 60.0, "appends": []}

Arrivals are Poisson: exponential gaps, as ``poisson_arrivals`` draws
them.  The gap sizes come from the mix's ``gap_seed`` and only their order
from the run seed, so every seed offers the same number of queries in the
window and in the pre-roll, in another order.  Sources are drawn from the
run seed over the vertices with out-arcs (Graph500's search-key rule):
uniformly with replacement, or Zipf(a) over a seeded ranking of them.
"""
from __future__ import annotations

import numpy as np

from .graph500 import SOURCES, ARRIVALS, Graph, rng_for


def poisson_arrivals(rate_qps: float, n: int, seed: int) -> np.ndarray:
    """Arrival offsets (seconds from t0) of an open-loop Poisson stream.

    Copied from the repository's ``benchmarks/common.py``."""
    if rate_qps <= 0:
        raise ValueError(f"offered rate must be positive, got {rate_qps}")
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate_qps, size=n))


def _span_times(rate: float, span: float, gap_seed: int,
                rng: np.random.Generator) -> np.ndarray:
    """``round(rate * span)`` arrival offsets in [0, span): fixed gap sizes,
    seeded order, scaled so that one more gap would end the span."""
    k = int(round(rate * span))
    if k == 0:
        return np.zeros(0)
    gaps = np.diff(poisson_arrivals(rate, k + 1, gap_seed), prepend=0.0)
    t = np.cumsum(rng.permutation(gaps))
    return t[:k] / t[-1] * span


def schedule(mix: dict, seconds: float, graph: Graph,
             seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(due offsets in seconds from the window's start, negative in the
    pre-roll; source vertices), sorted by due time."""
    if mix.get("appends"):
        raise NotImplementedError("mixes with appends are not generated yet")
    arr = mix["arrivals"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    rng = rng_for(seed, ARRIVALS)
    pre = _span_times(arr["rate_qps"], mix["preroll_s"], arr["gap_seed"] + 1,
                      rng) - mix["preroll_s"]
    win = _span_times(arr["rate_qps"], seconds, arr["gap_seed"], rng)
    due = np.concatenate([pre, win])
    cand = np.flatnonzero(graph.out_degree > 0)
    src_rng = rng_for(seed, SOURCES)
    dist = mix["sources"]
    if dist["dist"] == "uniform":
        srcs = cand[src_rng.integers(0, len(cand), len(due))]
    elif dist["dist"] == "zipf":
        ranks = np.minimum(src_rng.zipf(dist["a"], len(due)), len(cand)) - 1
        srcs = cand[src_rng.permutation(len(cand))[ranks]]
    else:
        raise ValueError(f"unknown source distribution {dist['dist']!r}")
    return due, srcs
