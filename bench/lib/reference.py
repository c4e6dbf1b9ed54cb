"""The plain reference: scipy.sparse.csgraph answers for single-source
REACH and SSSP, independent of the code under test.

The program's semantics: paths of at least one arc, so a source is in its
own answer only on a cycle; parallel arcs keep their lightest weight.
Copied from the repository's chip smoke test (``Reference`` there).
"""
from __future__ import annotations

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import breadth_first_order, dijkstra


class Reference:
    def __init__(self, n: int, arcs: np.ndarray):
        self.n = n
        if arcs.shape[1] == 2:
            ones = np.ones(len(arcs), np.float64)
            self.adj = csr_matrix((ones, (arcs[:, 0], arcs[:, 1])),
                                  shape=(n, n))
            self.adj_t = self.adj.T.tocsr()
            return
        order = np.lexsort((arcs[:, 2], arcs[:, 1], arcs[:, 0]))
        w = arcs[order]
        first = np.ones(len(w), bool)
        first[1:] = (w[1:, 0] != w[:-1, 0]) | (w[1:, 1] != w[:-1, 1])
        w = w[first]
        self.w = csr_matrix((w[:, 2].astype(np.float64), (w[:, 0], w[:, 1])),
                            shape=(n, n))
        self.w_t = self.w.T.tocsr()

    def reach(self, s: int) -> np.ndarray:
        """Sorted vertices reachable from ``s`` by one arc or more."""
        seen = breadth_first_order(self.adj, s, directed=True,
                                   return_predecessors=False)
        out = seen[seen != s]
        if np.isin(self.adj_t[s].indices, seen).any():  # back on a cycle
            out = np.append(out, s)
        return np.sort(out)

    def dpath(self, s: int, dtype=np.float64) -> tuple[np.ndarray, np.ndarray]:
        """(sorted destinations, their shortest distances) from ``s``."""
        dist = dijkstra(self.w, directed=True, indices=s)
        back = self.w_t[s]
        loop = dist[back.indices] + back.data
        dist[s] = loop.min() if len(loop) else np.inf
        dst = np.nonzero(np.isfinite(dist))[0]
        return dst, dist[dst]
