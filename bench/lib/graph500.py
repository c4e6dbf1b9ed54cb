"""The Graph500 Kronecker generator (specification section 3), vectorized.

The weighted edge structure comes from the configuration's ``graph_seed``,
so every run of a configuration serves the same graph up to its labels:
the same degree profile, so the same compiled shapes, and the same
shortest-path depths, so the same work.  The run's ``--seed`` relabels the
vertices (the specification's random vertex permutation): each seed serves
the same sizes in another order.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any integer seed."""
    return np.random.default_rng([seed & ((1 << 64) - 1), stream])


#: streams of the run seed (one generator per use, so adding a use never
#: shifts another's draws)
LABELS, WEIGHTS, ARRIVALS, SOURCES, SAMPLE = range(5)


def kronecker_arcs(scale: int, edgefactor: int, a: float, b: float,
                   c: float, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """``edgefactor * 2**scale`` Kronecker arcs as in the Graph500 reference
    generator, before the vertex permutation; directed, with self-loops and
    duplicate arcs dropped (one ``np.unique`` over packed keys), sorted."""
    rng = rng_for(seed, 0)
    m = edgefactor << scale
    ab = a + b
    c_norm, a_norm = c / (1.0 - ab), a / ab
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        src |= ii.astype(np.int64) << bit
        dst |= jj.astype(np.int64) << bit
    keep = src != dst
    key = np.unique((src[keep] << scale) | dst[keep])
    return key >> scale, key & ((1 << scale) - 1)


@dataclasses.dataclass(frozen=True)
class Graph:
    """One served graph: ``arcs`` (m, 2) or, weighted, (m, 3) int64."""

    n: int
    arcs: np.ndarray

    @property
    def out_degree(self) -> np.ndarray:
        return np.bincount(self.arcs[:, 0], minlength=self.n)


def permutation(n: int, has_arcs: np.ndarray, seed: int) -> np.ndarray:
    """A seeded vertex relabelling that gives the top label ``n - 1`` to a
    vertex with arcs, so the served domain is ``n`` for every seed."""
    perm = rng_for(seed, LABELS).permutation(n)
    top = int(np.flatnonzero(perm == n - 1)[0])
    if not has_arcs[top]:
        other = int(np.flatnonzero(has_arcs)[0])
        perm[top], perm[other] = perm[other], perm[top]
    return perm


def generate(gen: dict, seed: int) -> Graph:
    """The configuration's graph under the run seed's labels.

    ``gen`` is the configuration's ``generator`` group: ``scale``,
    ``edgefactor``, ``A``, ``B``, ``C``, ``graph_seed`` and, for weighted
    arcs, ``weights: [lo, hi]`` (integers, both ends included)."""
    n = 1 << gen["scale"]
    src, dst = kronecker_arcs(gen["scale"], gen["edgefactor"], gen["A"],
                              gen["B"], gen["C"], gen["graph_seed"])
    has_arcs = np.zeros(n, bool)
    has_arcs[src] = has_arcs[dst] = True
    perm = permutation(n, has_arcs, seed)
    cols = [perm[src], perm[dst]]
    if gen.get("weights"):
        lo, hi = gen["weights"]
        cols.append(rng_for(gen["graph_seed"], WEIGHTS).integers(
            lo, hi + 1, len(src)))
    return Graph(n=n, arcs=np.stack(cols, axis=1))


def sinks(graph: Graph) -> np.ndarray:
    """Vertices with no out-arcs: a flush of them closes in one iteration,
    which makes warm-up flushes cheap and leaves the compiled programs the
    same as for any other flush of that width."""
    return np.flatnonzero(graph.out_degree == 0)
