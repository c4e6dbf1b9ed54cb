"""The copied scipy reference against the repository's naive oracle."""
import sys
import os

import numpy as np
import pytest

from bench.lib import graph500
from bench.lib.reference import Reference

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "tests"))
from _reference import ref_model  # noqa: E402
from repro.core.parser import parse_program  # noqa: E402

GEN = {"scale": 5, "edgefactor": 4, "A": 0.57, "B": 0.19, "C": 0.19,
       "graph_seed": 3}


@pytest.mark.parametrize("seed", [0, 2**31 + 1])
def test_reach_matches_oracle(seed):
    g = graph500.generate({**GEN, "weights": None}, seed)
    prog = parse_program("reach(X,Y) <- arc(X,Y).\n"
                         "reach(X,Y) <- reach(X,Z), arc(Z,Y).")
    model = ref_model(prog, {"arc": {tuple(r) for r in g.arcs.tolist()}})
    ref = Reference(g.n, g.arcs)
    for s in np.unique(g.arcs[:, 0])[:12]:
        want = sorted(y for x, y in model["reach"] if x == s)
        assert ref.reach(int(s)).tolist() == want


@pytest.mark.parametrize("seed", [0, 2**31 + 1])
def test_dpath_matches_oracle(seed):
    g = graph500.generate({**GEN, "weights": [1, 9]}, seed)
    prog = parse_program(
        "dpath(X,Z,min<D>) <- warc(X,Z,D).\n"
        "dpath(X,Z,min<D>) <- dpath(X,Y,Dxy), warc(Y,Z,Dyz), D = Dxy + Dyz.")
    model = ref_model(prog, {"warc": {tuple(r) for r in g.arcs.tolist()}})
    ref = Reference(g.n, g.arcs)
    for s in np.unique(g.arcs[:, 0])[:12]:
        want = sorted((z, d) for x, z, d in model["dpath"] if x == s)
        dst, dist = ref.dpath(int(s))
        assert list(zip(dst.tolist(), dist.astype(int).tolist())) == want
