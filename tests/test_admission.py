"""Async admission front-end: coalescing, fencing, shedding, short-circuits.

Equivalence bar: every answer produced through the continuous-batching
dispatcher — whatever flush composition the arrival timing produced — must
equal the corresponding independent ``Engine.ask()``.  The other invariants
are operational: mixed shapes never share a fixpoint, cache hits resolve at
submit time, a full queue sheds with a typed error, and an ``append`` racing
an in-flight flush is fenced (pre-append answers never get tagged with the
post-append epoch).
"""
import threading
import time

import numpy as np
import pytest

from repro.core.engine import Engine
from repro.service import (AsyncDatalogService, DatalogService,
                           QueueFullError)

TC = """
tc(X,Y) <- arc(X,Y).
tc(X,Y) <- tc(X,Z), arc(Z,Y).
"""

SG = """
sg(X,Y) <- arc(P,X), arc(P,Y), X != Y.
sg(X,Y) <- arc(A,X), sg(A,B), arc(B,Y).
"""

EDGES = np.array([[0, 1], [1, 2], [2, 3], [3, 1], [4, 0], [5, 6], [2, 5],
                  [6, 7], [7, 8], [0, 4], [3, 7]])


def rows_set(rows):
    return {tuple(map(int, r)) for r in rows}


def test_concurrent_submitters_match_sequential_ask():
    """8 threads × 4 queries race the dispatcher; every answer must be
    bit-identical to a solo ``Engine.ask`` (the dense formatter is
    order-deterministic per source, so exact array equality holds no matter
    which flush a query landed in)."""
    eng = Engine(TC, db={"arc": EDGES}, default_cap=2048)
    front = AsyncDatalogService(
        DatalogService(TC, db={"arc": EDGES}, default_cap=2048),
        max_wait_ms=1.0, max_batch=8)
    sources = [0, 1, 2, 3, 4, 5, 6, 7]
    results: dict = {}

    def worker(s):
        out = []
        for _ in range(4):
            out.append(front.ask(("tc", (s, None)), timeout=60))
        results[s] = out

    threads = [threading.Thread(target=worker, args=(s,)) for s in sources]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for s in sources:
        want = eng.ask("tc", (s, None))
        for got in results[s]:
            assert np.array_equal(np.asarray(got), np.asarray(want)), s
    rep = front.explain()["admission"]["counters"]
    assert rep["submitted"] == 32 and rep["shed"] == 0
    assert rep["completed"] + rep["short_circuits"] == 32
    front.close()


def test_mixed_shapes_interleave_without_cross_coalescing():
    """tc (dense single-source) and sg (tuple-path) queries submitted
    interleaved: a flush containing both shapes must route each group to its
    own fixpoint — dense and tuple stats both move, and every answer still
    matches the solo engine."""
    program = TC + SG
    eng = Engine(program, db={"arc": EDGES}, default_cap=2048)
    svc = DatalogService(program, db={"arc": EDGES}, default_cap=2048)
    front = AsyncDatalogService(svc, max_wait_ms=50.0, max_batch=16,
                                start=False)
    queries = []
    for s in (0, 2, 3, 1):
        queries.append(("tc", (s, None)))
        queries.append(("sg", (s, None)))
    futs = [front.submit(q) for q in queries]  # staged before dispatch runs
    front.start()
    answers = [f.result(timeout=60) for f in futs]
    # one window held all 8 queries -> exactly one flush, two shape groups
    assert front.stats.flushes == 1 and front.stats.max_flush == 8
    assert svc.stats.dense_fixpoints == 1  # the 4 tc queries, coalesced
    assert svc.stats.tuple_fixpoints >= 1  # the 4 sg queries, separately
    for q, got in zip(queries, answers):
        assert rows_set(got) == rows_set(eng.ask(*q)), q
    front.close()


def test_cache_hit_short_circuits_at_submit():
    front = AsyncDatalogService(
        DatalogService(TC, db={"arc": EDGES}, default_cap=2048),
        max_wait_ms=1.0, max_batch=8)
    first = front.ask(("tc", (2, None)), timeout=60)
    flushes = front.stats.flushes
    fut = front.submit(("tc", (2, None)))
    assert fut.done(), "cache hit must resolve before submit returns"
    assert np.array_equal(np.asarray(fut.result()), np.asarray(first))
    assert front.stats.short_circuits == 1
    front.drain()
    assert front.stats.flushes == flushes, \
        "short-circuit must not occupy a batch slot"
    front.close()


def test_queue_full_sheds_with_typed_error():
    front = AsyncDatalogService(
        DatalogService(TC, db={"arc": EDGES}, default_cap=2048),
        queue_depth=3, start=False)
    for s in (0, 1, 2):
        front.submit(("tc", (s, None)))
    with pytest.raises(QueueFullError) as exc:
        front.submit(("tc", (3, None)))
    assert exc.value.depth == 3
    assert front.stats.shed == 1 and front.stats.submitted == 3
    # malformed queries fail the caller synchronously, not the shared flush
    with pytest.raises(Exception):
        front.submit("no_such_pred(1, X)")
    front.start()
    front.drain()
    assert front.stats.completed == 3  # the shed/bad ones never queued
    front.close()


def test_append_racing_inflight_flush_is_epoch_fenced():
    """Submit a burst, immediately append from the test thread: the fence
    must drain the in-flight flushes BEFORE the epoch bumps (launch/finalize
    asserts would trip otherwise), post-append queries see the new facts,
    and the refreshed cache serves post-append answers."""
    front = AsyncDatalogService(
        DatalogService(TC, db={"arc": EDGES}, default_cap=2048),
        max_wait_ms=1.0, max_batch=4)
    pre_futs = [front.submit(("tc", (s, None))) for s in (0, 1, 2, 3, 4, 5)]
    front.append("arc", [[8, 0]])  # races the in-flight flushes
    assert front.epoch == 1
    post_futs = [front.submit(("tc", (s, None))) for s in (6, 7, 8)]
    pre = [f.result(timeout=60) for f in pre_futs]
    post = [f.result(timeout=60) for f in post_futs]

    eng_pre = Engine(TC, db={"arc": EDGES}, default_cap=2048)
    appended = np.vstack([EDGES, [[8, 0]]])
    eng_post = Engine(TC, db={"arc": appended}, default_cap=2048)
    for s, got in zip((0, 1, 2, 3, 4, 5), pre):
        # a pre-append future resolves against whichever epoch its flush
        # ran under — both are correct models, torn answers are neither
        want_pre = rows_set(eng_pre.ask("tc", (s, None)))
        want_post = rows_set(eng_post.ask("tc", (s, None)))
        assert rows_set(got) in (want_pre, want_post), s
    for s, got in zip((6, 7, 8), post):  # post-append: new facts visible
        assert rows_set(got) == rows_set(eng_post.ask("tc", (s, None))), s
    # the cache refreshed under the fence: re-asks serve post-append answers
    for s in (0, 1, 2, 3, 4, 5):
        got = front.ask(("tc", (s, None)), timeout=60)
        assert rows_set(got) == rows_set(eng_post.ask("tc", (s, None))), s
    front.close()


def test_append_under_sustained_load_stays_consistent():
    """Interleave appends with a stream of concurrent submitters; every
    final re-ask must reflect ALL appended facts (no lost appends, no stale
    cache survivors, no fence deadlock)."""
    front = AsyncDatalogService(
        DatalogService(TC, db={"arc": EDGES}, default_cap=2048),
        max_wait_ms=1.0, max_batch=8)
    new_edges = [[8, 1], [7, 0], [6, 3]]
    stop = threading.Event()
    errors: list = []

    def submitter(seed):
        rng = np.random.default_rng(seed)
        while not stop.is_set():
            try:
                front.ask(("tc", (int(rng.integers(0, 9)), None)), timeout=60)
            except Exception as e:  # pragma: no cover - diagnostic
                errors.append(e)
                return

    threads = [threading.Thread(target=submitter, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for row in new_edges:
        time.sleep(0.01)
        front.append("arc", [row])
    stop.set()
    for t in threads:
        t.join()
    assert not errors, errors[:1]
    assert front.epoch == len(new_edges)
    final = np.vstack([EDGES] + [[r] for r in new_edges])
    eng = Engine(TC, db={"arc": final}, default_cap=2048)
    for s in range(9):
        got = front.ask(("tc", (s, None)), timeout=60)
        assert rows_set(got) == rows_set(eng.ask("tc", (s, None))), s
    front.close()


def test_max_batch_bounds_every_launch_the_service_sizes():
    """The front end's ``max_batch`` becomes the service's widest batch pad,
    so append resumes launch no wider than a flush; a ``max_batch`` wider
    than the widest pad is refused."""
    svc = DatalogService(TC, db={"arc": EDGES}, default_cap=2048)
    assert svc.batch_pads == (1, 8, 32, 128)
    with pytest.raises(ValueError, match="widest batch pad"):
        AsyncDatalogService(svc, max_batch=256, start=False)
    front = AsyncDatalogService(svc, max_wait_ms=1.0, max_batch=4)
    assert svc.batch_pads == (1, 4)
    sources = list(range(9))
    for s in sources:
        front.ask(("tc", (s, None)), timeout=60)
    before = svc.stats.dense_fixpoints
    front.append("arc", [[8, 0]])
    assert svc.stats.resumed_rows == len(sources)
    assert svc.stats.dense_fixpoints - before == 3  # 4 + 4 + 1 rows
    eng = Engine(TC, db={"arc": np.vstack([EDGES, [[8, 0]]])},
                 default_cap=2048)
    for s in sources:
        got = front.ask(("tc", (s, None)), timeout=60)
        assert rows_set(got) == rows_set(eng.ask("tc", (s, None))), s
    front.close()
