"""Useful work of a flush: the bytes any correct implementation has to
move, whatever implements it.

Per flush, over its live queries (those launched into the fixpoint):

- read: the union of the arcs out of the vertices the queries reach, their
  sources included, each read once at 4 B, plus 4 B for a min-plus weight;
- write: each query's answer once, at the smaller of its sparse size (4 B
  per vertex, 8 B with a distance) and a dense row (an ``n``-bit bitmap
  for bool, ``4 n`` B for distances).

No padding, pad rows, iteration count or intermediate array is counted, so
no correct implementation can read above 100% of the roofline with it.
"""
from __future__ import annotations

import numpy as np


def flush_bytes(out_degree: np.ndarray, live: list, weighted: bool) -> float:
    """``live``: per live query of the flush, (source, answer vertex ids)."""
    if not live:
        return 0.0
    per = 8 if weighted else 4
    union = np.unique(np.concatenate(
        [np.asarray([s for s, _ in live])] + [np.asarray(a) for _, a in live]))
    read = float(out_degree[union].sum()) * per
    n = len(out_degree)
    dense = 4 * n if weighted else -(-n // 8)
    write = sum(min(len(a) * per, dense) for _, a in live)
    return read + float(write)
