"""The Graph500 generator: determinism, sizes, and shapes that do not move
with the run seed."""
import numpy as np
import pytest

from bench.lib import graph500

GEN = {"edgefactor": 16, "A": 0.57, "B": 0.19, "C": 0.19, "graph_seed": 1,
       "weights": None}


def gen(scale, seed, **kw):
    return graph500.generate({**GEN, "scale": scale, **kw}, seed)


def test_same_seed_same_graph():
    a, b = gen(12, 5, weights=[1, 65535]), gen(12, 5, weights=[1, 65535])
    assert np.array_equal(a.arcs, b.arcs)


def test_seed_relabels_only():
    a, b = gen(12, 5, weights=[1, 9]), gen(12, 2**31 + 9, weights=[1, 9])
    assert not np.array_equal(a.arcs, b.arcs)
    assert len(a.arcs) == len(b.arcs)
    # the same degree profiles, so the same sliced-ELL shapes
    for col in (0, 1):
        da = np.sort(np.bincount(a.arcs[:, col], minlength=a.n))
        db = np.sort(np.bincount(b.arcs[:, col], minlength=b.n))
        assert np.array_equal(da, db)
    # the top label always carries an arc: the served domain is n
    assert a.arcs[:, :2].max() == b.arcs[:, :2].max() == a.n - 1
    assert a.arcs[:, 2].min() >= 1 and a.arcs[:, 2].max() <= 9
    # the same weighted graph under other labels: arc k of one is arc k of
    # the other, relabelled by one bijection, with the same weight
    assert np.array_equal(a.arcs[:, 2], b.arcs[:, 2])
    pi = np.full(a.n, -1)
    pi[a.arcs[:, :2].ravel()] = b.arcs[:, :2].ravel()
    assert np.array_equal(pi[a.arcs[:, :2]], b.arcs[:, :2])
    used = pi[pi >= 0]
    assert len(np.unique(used)) == len(used)


def test_arcs_are_directed_distinct_and_loop_free():
    g = gen(10, 3)
    key = g.arcs[:, 0] * g.n + g.arcs[:, 1]
    assert len(np.unique(key)) == len(key)
    assert not (g.arcs[:, 0] == g.arcs[:, 1]).any()


@pytest.mark.parametrize("scale,arcs,with_out", [
    (16, 955_344, 40_411), (17, 1_942_464, 77_755), (18, 3_939_757, 148_813)])
def test_sizes(scale, arcs, with_out):
    """|E| and the vertices with out-arcs at the configurations' scales
    (about 3.94 M arcs and 148.6 k such vertices at scale 18)."""
    g = gen(scale, 0)
    assert len(g.arcs) == arcs
    assert int((g.out_degree > 0).sum()) == with_out


def test_sinks_have_no_out_arcs():
    g = gen(10, 1)
    s = graph500.sinks(g)
    assert len(s) and not np.isin(s, g.arcs[:, 0]).any()
