"""The flush-stage readers (queue wait, in-flight time, pad-row share,
active-row share): each on a hand-made record, on a record of a program
without their spans, and on the spans of a CPU run of the admission
front-end with a recording tracer."""
import os

import numpy as np
import pytest

from bench.lib import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
NEW = ["admission.queue_wait_p50_ms", "session.inflight_p50_ms",
       "session.pad_row_share", "fixpoint.active_row_share"]


def span(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": 1,
            "tid": 1, **({"args": args} if args else {})}


#: two flushes: #1 of 3 queries (8 rows, 4 iterations), #2 of 1 query
#: (8 rows, 2 iterations); times in microseconds
HAND = {"spans": [
    span("flush", 0, 500, queue_wait_ms=[4.0, 2.0, 1.0], flush=1),
    span("launch_batch", 100, 300, batch=3, flush=1, ahead=0),
    span("flush", 600, 200, queue_wait_ms=[30.0], flush=2),
    span("launch_batch", 700, 50, batch=1, flush=2, ahead=1),
    span("finalize_batch", 1000, 9000, batch=3, flush=1),
    span("device_sync", 1100, 4900, pred="tc", flush=1, rows=3, pad=8,
         iterations=4, active_rows=14, delta_facts=20),
    span("device_sync", 10500, 500, pred="tc", flush=2, rows=1, pad=8,
         iterations=2, active_rows=-1, delta_facts=-1),
]}
#: the same flushes as a program without the flush-stage spans traces them
BARE = {"spans": [
    span("coalesce", 0, 500, batch=3),
    span("launch_batch", 100, 300, batch=3),
    span("device_sync", 1100, 4900, pred="tc"),
    span("finalize_batch", 1000, 9000, batch=3),
]}


@pytest.mark.parametrize("name,want", [
    ("admission.queue_wait_p50_ms", 3.0),  # median of 4, 2, 1, 30
    ("session.inflight_p50_ms", 5.9),  # 5.9 ms x 3 queries, 10.3 ms x 1
    ("session.pad_row_share", 12 / 16),  # 4 live rows of 16
    ("fixpoint.active_row_share", 14 / 32),  # flush 2 did not count rows
])
def test_reader_on_a_hand_made_record(name, want):
    read = spec.metric_reader(ROOT, name)
    assert read(HAND) == pytest.approx(want)
    assert read(BARE) is None
    assert read({"spans": []}) is None


def test_readers_on_a_traced_admission_run():
    """Spans of a CPU run through ``AsyncDatalogService`` with a recording
    tracer: every reader gives a value in range, and each flush's
    ``launch_batch`` pairs with its own ``device_sync`` by number."""
    from repro.obs import Tracer
    from repro.service import AsyncDatalogService, DatalogService

    rng = np.random.default_rng(5)
    arcs = rng.integers(0, 200, size=(800, 2))
    tracer = Tracer()
    svc = DatalogService("tc(X,Y) <- arc(X,Y).\ntc(X,Y) <- tc(X,Z), arc(Z,Y).",
                         db={"arc": arcs}, sparse=True, batch_pads=(4, 16),
                         result_cache=0, tracer=tracer)
    front = AsyncDatalogService(svc, max_wait_ms=5.0, max_batch=16,
                                inflight=2)
    futs = [front.submit(f"tc({s}, X)") for s in rng.integers(0, 200, 40)]
    for f in futs:
        f.result(timeout=300)
    front.close()
    rec = {"spans": tracer.spans()}
    got = {n: spec.metric_reader(ROOT, n)(rec) for n in NEW}
    assert got["admission.queue_wait_p50_ms"] >= 0
    assert got["session.inflight_p50_ms"] > 0
    assert 0 <= got["session.pad_row_share"] < 1
    assert 0 < got["fixpoint.active_row_share"] <= 1
    by = {}
    for s in rec["spans"]:
        by.setdefault(s["name"], []).append(s)
    launches = {s["args"]["flush"]: s for s in by["launch_batch"]}
    syncs = {s["args"]["flush"]: s for s in by["device_sync"]}
    assert len(syncs) == len(by["device_sync"])  # one dense group a flush
    assert syncs.keys() == launches.keys() == {
        s["args"]["flush"] for s in by["flush"]}
    for f, s in syncs.items():
        assert s["ts"] >= launches[f]["ts"]
        assert 0 <= launches[f]["args"]["ahead"] < f
        assert s["args"]["rows"] <= s["args"]["pad"]
    assert sum(len(s["args"]["queue_wait_ms"]) for s in by["flush"]) == 40
    assert sum(s["args"]["batch"] for s in by["launch_batch"]) == 40
