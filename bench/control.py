#!/usr/bin/env python3
"""The control of a cell's check: the plain reference put in the program's
place, broken the way a later change might be tempted to break it, has to
come out as not correct.

    python bench/control.py --workload <cell> --seeds a,b,c [--seconds 30]

For each seed it makes the graph and the schedule a run of the cell makes,
answers the window's queries with the control, and checks them exactly as
a run checks what the service served (``harness.check``).  It prints one
JSON line per seed with ``wrong_answers``: the upper reading of that
number's limit.  The control:

- min-plus carrier (float32 in the program): the reference in bfloat16,
  the precision below: weights and every relaxed distance are rounded;
- bool carrier (no lower precision): the closure stopped one round short
  of its fixpoint, which breaks the exact-answer guarantee.

It needs no chip: the reference runs on the host.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bf16(x: np.ndarray) -> np.ndarray:
    """``x`` rounded to bfloat16 and back (round to nearest even)."""
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


class Control:
    """``exact=True`` makes the same code a sound twin of the reference (no
    rounding, the whole closure): the tests' witness that the control reads
    wrong for the reason named and for no other."""

    def __init__(self, graph, exact: bool = False):
        self.exact = exact
        self.round = (lambda x: np.asarray(x, np.float32)) if exact else bf16
        self.n = graph.n
        arcs = graph.arcs
        order = np.argsort(arcs[:, 1], kind="stable")
        self.src = arcs[order, 0]
        self.dst = arcs[order, 1]
        self.w = self.round(arcs[order, 2]) if arcs.shape[1] == 3 else None
        self.starts = np.flatnonzero(np.r_[True, self.dst[1:] != self.dst[:-1]])

    def dpath(self, s: int):
        """Bellman-Ford from ``s`` with every distance held in bfloat16."""
        dist = np.full(self.n, np.inf, np.float32)
        seed = self.src == s
        np.minimum.at(dist, self.dst[seed], self.w[seed])
        while True:
            cand = self.round(dist[self.src] + self.w)
            best = np.minimum.reduceat(cand, self.starts)
            heads = self.dst[self.starts]
            new = dist.copy()
            new[heads] = np.minimum(dist[heads], best)
            if np.array_equal(new, dist):
                break
            dist = new
        dst = np.flatnonzero(np.isfinite(dist))
        rows = np.stack([np.full(len(dst), s), dst], axis=1)
        return rows, dist[dst].astype(np.int64)

    def reach(self, s: int):
        """The closure from ``s`` one round short: the farthest level of
        its breadth-first search left out."""
        frontier = np.unique(self.dst[self.src == s])
        levels = [frontier]
        seen = np.zeros(self.n, bool)
        seen[frontier] = True
        while len(frontier):
            nxt = np.unique(self.dst[np.isin(self.src, frontier)])
            frontier = nxt[~seen[nxt]]
            seen[frontier] = True
            levels.append(frontier)
        levels = [lv for lv in levels if len(lv)]
        if self.exact:
            levels.append(np.zeros(0, np.int64))
        keep = np.sort(np.concatenate(levels[:-1])) if len(levels) > 1 \
            else np.zeros(0, np.int64)
        return np.stack([np.full(len(keep), s), keep], axis=1)


def control_run(cell, seed: int, seconds: float, exact: bool = False) -> dict:
    from bench.lib import graph500, harness, traffic

    cfg = cell.config
    graph = graph500.generate(cfg, seed)
    due, srcs = traffic.schedule(cell.mix, seconds, graph, seed)
    ctl = Control(graph, exact)
    answer = ctl.reach if cfg["arity"] == 2 else ctl.dpath
    sample = harness.sample_sources(srcs[due >= 0], cfg["check_sample"], seed)
    got = harness.check(cfg, graph,
                        {s: (lambda s=s: answer(s)) for s in sample})
    return {"seed": seed, "scale": cfg["scale"], **got}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench.lib import spec

    cell = spec.load(ROOT, args.workload)
    for seed in args.seeds.split(","):
        print(json.dumps(control_run(cell, int(seed), args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
