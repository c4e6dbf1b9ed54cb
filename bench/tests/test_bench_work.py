"""The useful-bytes count against a hand count on a 6-vertex graph."""
import numpy as np

from bench.lib import work
from bench.lib.reference import Reference

#: 0 -> 1 -> 2 -> 0 is a cycle, 0 -> 3 leads to a sink, 4 -> 5 stands apart
ARCS = np.array([[0, 1], [1, 2], [2, 0], [0, 3], [4, 5]])
OUT = np.bincount(ARCS[:, 0], minlength=6)  # [2, 1, 1, 0, 1, 0]


def flush():
    ref = Reference(6, ARCS)
    return [(0, ref.reach(0)), (4, ref.reach(4))]


def test_answers_are_what_the_hand_count_assumes():
    (_, a0), (_, a4) = flush()
    assert a0.tolist() == [0, 1, 2, 3] and a4.tolist() == [5]


def test_bool_flush_bytes():
    # reads: every vertex is a source or reached, 5 arcs at 4 B = 20;
    # writes: a 6-vertex bitmap is 1 B, smaller than 16 B or 4 B sparse
    assert work.flush_bytes(OUT, flush(), weighted=False) == 20 + 1 + 1


def test_weighted_flush_bytes():
    # reads: 5 arcs at 8 B = 40; writes: min(4 * 8, 24) + min(1 * 8, 24)
    assert work.flush_bytes(OUT, flush(), weighted=True) == 40 + 24 + 8


def test_shared_vertices_are_read_once():
    one = work.flush_bytes(OUT, flush()[:1], weighted=False)
    twice = work.flush_bytes(OUT, flush()[:1] * 2, weighted=False)
    assert twice - one == 1  # only the second answer's write


def test_empty_flush():
    assert work.flush_bytes(OUT, [], weighted=False) == 0.0
