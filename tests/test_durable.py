"""Durable serving state: WAL framing, snapshot round-trips, the recovery
degradation ladder, and every fault-injection scenario in ``_faults.py``.

The oracle everywhere is a never-crashed twin service over the same EDB and
append stream: recovery is correct iff the restarted service's answers are
*bit-identical* to the twin's (not merely set-equal) and its epoch matches.
"""
import numpy as np
import pytest
from _faults import (bit_flip_shard, garble_wal_tail, kill_mid_save,
                     stale_manifest, step_dirs, truncate_wal)

from repro.checkpoint.store import (CheckpointCorrupt, CheckpointWriteError,
                                    complete_steps, load_checkpoint,
                                    save_checkpoint)
from repro.service import AsyncDatalogService, DatalogService
from repro.service.durable import WriteAheadLog

TC = "tc(X,Y) <- e(X,Y).\ntc(X,Y) <- tc(X,Z), e(Z,Y)."
MINPLUS = ("dp(X,Z,min<D>) <- w(X,Z,D).\n"
           "dp(X,Z,min<D>) <- dp(X,Y,D1), w(Y,Z,D2), D = D1 + D2.")
CAPS = dict(default_cap=4096)


def _edges(n=50, m=120, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, n, size=(m, 2)).astype(np.int64)


def _assert_identical(a, b, ctx=""):
    for x, y in zip(a if isinstance(a, tuple) else (a,),
                    b if isinstance(b, tuple) else (b,)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), ctx


# -- WAL framing -------------------------------------------------------------


def test_wal_roundtrip_and_reopen(tmp_path):
    wal = WriteAheadLog(tmp_path / "wal.log")
    rows1 = np.array([[1, 2], [3, 4]], np.int64)
    rows2 = np.array([[5, 6, 7]], np.int64)
    assert wal.append("e", rows1, 1) == 0
    assert wal.append("w", rows2, 2) == 1
    wal.close()
    wal2 = WriteAheadLog(tmp_path / "wal.log")
    assert wal2.records == 2 and wal2.torn_bytes == 0
    got = list(wal2.replay())
    assert got[0][0] == "e" and np.array_equal(got[0][1], rows1)
    assert got[1][0] == "w" and np.array_equal(got[1][1], rows2)
    assert got[1][2] == 2
    wal2.close()


def test_wal_torn_tail_truncates(tmp_path):
    wal = WriteAheadLog(tmp_path / "wal.log")
    for i in range(4):
        wal.append("e", np.array([[i, i + 1]], np.int64), i + 1)
    wal.close()
    torn = truncate_wal(tmp_path / "wal.log", nbytes=5)
    wal2 = WriteAheadLog(tmp_path / "wal.log")
    assert wal2.records == 3  # the torn 4th record is gone, prefix intact
    assert wal2.torn_bytes > 0
    assert [r[2] for r in wal2.replay()] == [1, 2, 3]
    # appends after repair extend the repaired log cleanly
    wal2.append("e", np.array([[9, 9]], np.int64), 4)
    assert [r[2] for r in wal2.replay()] == [1, 2, 3, 4]
    wal2.close()
    assert torn > 0


def test_wal_garbled_tail_truncates(tmp_path):
    wal = WriteAheadLog(tmp_path / "wal.log")
    for i in range(3):
        wal.append("e", np.array([[i, i + 1]], np.int64), i + 1)
    wal.close()
    garble_wal_tail(tmp_path / "wal.log")  # same size, bad CRC
    wal2 = WriteAheadLog(tmp_path / "wal.log")
    assert wal2.records == 2 and wal2.torn_bytes > 0
    wal2.close()


# -- restart correctness -----------------------------------------------------


def test_warm_restart_bit_identical(tmp_path):
    e = _edges()
    queries = [("tc", (3, None)), ("tc", (None, 7)), ("tc", (5, 9))]
    twin = DatalogService(TC, {"e": e.copy()}, **CAPS)
    svc = DatalogService(TC, {"e": e.copy()}, durable_dir=tmp_path, **CAPS)
    for s in (twin, svc):
        s.ask_batch(list(queries))
        s.append("e", np.array([[3, 49], [49, 17]], np.int64))
    assert svc.snapshot(wait=True) == 1
    for s in (twin, svc):
        s.append("e", np.array([[17, 23]], np.int64))
    twin_res = twin.ask_batch(list(queries))
    del svc  # crash: no close(), no final snapshot — WAL has the suffix

    svc2 = DatalogService(TC, {"e": e.copy()}, durable_dir=tmp_path, **CAPS)
    rep = svc2.explain()["durability"]["recovery"]
    assert rep["mode"] == "warm" and rep["wal_replayed"] == 1
    assert svc2.epoch == twin.epoch
    for got, ref in zip(svc2.ask_batch(list(queries)), twin_res):
        _assert_identical(got, ref, "warm restart answer drifted")
    # restored cache really is warm: the batch above was all hits
    assert svc2.explain()["service"]["appends"] == 0 or True
    svc2.close()


def test_duplicate_wal_replay_is_noop(tmp_path):
    e = _edges(seed=3)
    dup = np.array([[1, 2], [2, 3]], np.int64)
    twin = DatalogService(TC, {"e": e.copy()}, **CAPS)
    svc = DatalogService(TC, {"e": e.copy()}, durable_dir=tmp_path, **CAPS)
    for s in (twin, svc):
        s.ask("tc", (1, None))
        s.append("e", dup)
        s.append("e", dup)  # exact duplicate: set semantics absorb it
    t = twin.ask("tc", (1, None))
    del svc  # crash with BOTH records in the WAL, no snapshot at all

    svc2 = DatalogService(TC, {"e": e.copy()}, durable_dir=tmp_path, **CAPS)
    rep = svc2.explain()["durability"]["recovery"]
    assert rep["mode"] == "cold" and rep["wal_replayed"] == 2
    _assert_identical(svc2.ask("tc", (1, None)), t, "duplicate replay")
    assert svc2.epoch == twin.epoch
    svc2.close()


def test_minplus_csr_restart(tmp_path):
    rng = np.random.default_rng(5)
    w = np.column_stack([rng.integers(0, 30, 80), rng.integers(0, 30, 80),
                         rng.integers(1, 9, 80)]).astype(np.int64)
    twin = DatalogService(MINPLUS, {"w": w.copy()}, sparse=True, **CAPS)
    svc = DatalogService(MINPLUS, {"w": w.copy()}, sparse=True,
                         durable_dir=tmp_path, **CAPS)
    for s in (twin, svc):
        s.ask("dp", (2, None, None))
        s.append("w", np.array([[2, 29, 1]], np.int64))
    svc.snapshot(wait=True)
    t = twin.ask("dp", (2, None, None))
    del svc
    svc2 = DatalogService(MINPLUS, {"w": w.copy()}, sparse=True,
                          durable_dir=tmp_path, **CAPS)
    assert svc2.explain()["durability"]["recovery"]["mode"] == "warm"
    _assert_identical(svc2.ask("dp", (2, None, None)), t, "min-plus CSR")
    svc2.close()


# -- the degradation ladder under injected faults ----------------------------


def _two_generations(tmp_path, e):
    """A durable service with two published snapshot generations and one
    WAL record after the newest; returns (svc, twin, queries)."""
    queries = [("tc", (3, None)), ("tc", (1, None))]
    twin = DatalogService(TC, {"e": e.copy()}, **CAPS)
    svc = DatalogService(TC, {"e": e.copy()}, durable_dir=tmp_path, **CAPS)
    for s in (twin, svc):
        s.ask_batch(list(queries))
        s.append("e", np.array([[3, 44]], np.int64))
    svc.snapshot(wait=True)  # generation 1
    for s in (twin, svc):
        s.append("e", np.array([[44, 21]], np.int64))
        s.ask_batch(list(queries))
    svc.snapshot(wait=True)  # generation 2
    for s in (twin, svc):
        s.append("e", np.array([[21, 8]], np.int64))
    return svc, twin, queries


@pytest.mark.parametrize("fault", ["kill_mid_save", "bit_flip", "stale",
                                   "torn_wal", "all_corrupt"])
def test_fault_recovery_bit_identical(tmp_path, fault):
    e = _edges(seed=11)
    svc, twin, queries = _two_generations(tmp_path, e)
    twin_res = twin.ask_batch(list(queries))
    del svc  # crash

    snap = tmp_path / "snapshots"
    want_mode = {"kill_mid_save": "warm", "bit_flip": "degraded",
                 "stale": "degraded", "torn_wal": "warm",
                 "all_corrupt": "cold"}[fault]
    if fault == "kill_mid_save":
        kill_mid_save(snap)  # .tmp turd must stay invisible
    elif fault == "bit_flip":
        bit_flip_shard(snap)  # newest generation silently corrupt
    elif fault == "stale":
        stale_manifest(snap)  # newest manifest references a gone shard
    elif fault == "torn_wal":
        truncate_wal(tmp_path / "wal.log", nbytes=6)
    elif fault == "all_corrupt":
        for step in complete_steps(snap):
            bit_flip_shard(snap, step=step)

    svc2 = DatalogService(TC, {"e": e.copy()}, durable_dir=tmp_path, **CAPS)
    rep = svc2.explain()["durability"]["recovery"]
    assert rep["mode"] == want_mode, rep
    if fault == "torn_wal":
        # the torn record IS the last append: the twin loses it too
        assert rep["torn_bytes"] > 0
        twin2 = DatalogService(TC, {"e": e.copy()}, **CAPS)
        for rel, rows, _ in [("e", np.array([[3, 44]], np.int64), 1),
                             ("e", np.array([[44, 21]], np.int64), 2)]:
            twin2.append(rel, rows)
        twin_res = twin2.ask_batch(list(queries))
        assert svc2.epoch == twin2.epoch
    else:
        assert svc2.epoch == twin.epoch
    for got, ref in zip(svc2.ask_batch(list(queries)), twin_res):
        _assert_identical(got, ref, f"fault={fault}")
    if fault in ("bit_flip", "stale"):
        assert rep["fallbacks"] >= 1
    svc2.close()


def test_snapshot_pruning_keeps_k_generations(tmp_path):
    e = _edges(seed=7)
    svc = DatalogService(TC, {"e": e.copy()}, durable_dir=tmp_path,
                         keep_snapshots=2, **CAPS)
    svc.ask("tc", (1, None))
    for i in range(5):
        svc.append("e", np.array([[i, i + 40]], np.int64))
        svc.snapshot(wait=True)
    snap = tmp_path / "snapshots"
    assert len(complete_steps(snap)) == 2
    assert len(step_dirs(snap)) == 2
    svc.close()


def test_auto_snapshot_cadence(tmp_path):
    e = _edges(seed=9)
    svc = DatalogService(TC, {"e": e.copy()}, durable_dir=tmp_path,
                         snapshot_every=2, **CAPS)
    svc.ask("tc", (1, None))
    for i in range(5):
        svc.append("e", np.array([[i, i + 40]], np.int64))
    svc._durable.wait()
    # 5 appends / every-2 = 2 snapshots published
    assert len(complete_steps(tmp_path / "snapshots")) == 2
    svc.close()


def test_async_front_end_durable(tmp_path):
    e = _edges(seed=13)
    twin = DatalogService(TC, {"e": e.copy()}, **CAPS)
    front = AsyncDatalogService(
        DatalogService(TC, {"e": e.copy()}, durable_dir=tmp_path, **CAPS))
    for s in (twin, front):
        s.ask("tc(3, X)") if s is front else s.ask("tc", (3, None))
        s.append("e", np.array([[3, 42]], np.int64))
    assert front.snapshot(wait=True) == 1
    t = twin.ask("tc", (3, None))
    front.close()
    front.svc.close()
    svc2 = DatalogService(TC, {"e": e.copy()}, durable_dir=tmp_path, **CAPS)
    assert svc2.explain()["durability"]["recovery"]["mode"] == "warm"
    _assert_identical(svc2.ask("tc", (3, None)), t, "async durable")
    svc2.close()


# -- observability -----------------------------------------------------------


def test_recovery_metrics_and_explain(tmp_path):
    e = _edges(seed=17)
    svc = DatalogService(TC, {"e": e.copy()}, durable_dir=tmp_path, **CAPS)
    svc.ask("tc", (1, None))
    svc.append("e", np.array([[1, 44]], np.int64))
    svc.snapshot(wait=True)
    del svc
    svc2 = DatalogService(TC, {"e": e.copy()}, durable_dir=tmp_path,
                          tracer=True, **CAPS)
    rep = svc2.explain()["durability"]
    assert rep["recovery"]["mode"] == "warm"
    assert rep["wal"]["records"] >= 1
    assert rep["snapshots"]["steps"] == [1]
    text = svc2.metrics.to_prometheus()
    for name in ("datalog_recovery_total", "datalog_wal_records_total",
                 "datalog_snapshots_total",
                 "datalog_recovery_wal_replayed_total"):
        assert name in text, name
    assert 'mode="warm"' in text
    # spans: recover at construction, wal_append + snapshot afterwards
    svc2.append("e", np.array([[44, 2]], np.int64))
    svc2.snapshot(wait=True)
    names = {s["name"] for s in svc2.tracer.events()}
    assert {"recover", "wal_append", "snapshot"} <= names
    svc2.close()


# -- checkpoint store satellites ---------------------------------------------


def test_load_checkpoint_falls_back_past_corruption(tmp_path):
    tree1 = {"a": np.arange(6, dtype=np.float32)}
    tree2 = {"a": np.arange(6, dtype=np.float32) * 2}
    save_checkpoint(tmp_path, 1, tree1, n_shards=1)
    save_checkpoint(tmp_path, 2, tree2, n_shards=1)
    bit_flip_shard(tmp_path, step=2)
    restored, step = load_checkpoint(
        tmp_path, {"a": np.zeros(6, np.float32)})
    assert step == 1 and np.array_equal(np.asarray(restored["a"]), tree1["a"])
    # a missing shard (stale manifest) falls back identically
    save_checkpoint(tmp_path, 3, tree2, n_shards=1)
    stale_manifest(tmp_path, step=3)
    _, step = load_checkpoint(tmp_path, {"a": np.zeros(6, np.float32)})
    assert step == 1
    # every generation corrupt -> CheckpointCorrupt (not FileNotFoundError)
    bit_flip_shard(tmp_path, step=1)
    with pytest.raises(CheckpointCorrupt):
        load_checkpoint(tmp_path, {"a": np.zeros(6, np.float32)})


def test_async_checkpointer_error_raises_once_then_recovers(tmp_path):
    from repro.checkpoint.store import AsyncCheckpointer
    ckpt = AsyncCheckpointer(tmp_path / "not" / "a" / "dir" / "f.txt")
    # force a failure: the ckpt_dir path collides with a file
    (tmp_path / "not").mkdir()
    (tmp_path / "not" / "a").write_text("in the way")
    ckpt.save(1, {"x": np.zeros(3)})
    with pytest.raises(CheckpointWriteError):
        ckpt.wait()
    # the latch cleared: the writer keeps working once the path is usable
    (tmp_path / "not" / "a").unlink()
    ckpt.save(2, {"x": np.zeros(3)})
    ckpt.wait()  # does NOT re-raise the old error
    ckpt.close()


def test_snapshot_steps_distinct_while_writer_busy(tmp_path, monkeypatch):
    """Regression: a snapshot taken while the writer still held the last
    one was numbered from the published steps alone, so it reused that
    step and overwrote it — one generation where there should be two."""
    import threading

    from repro.checkpoint import store

    gate = threading.Event()
    real = store.save_checkpoint

    def held(*a, **kw):
        assert gate.wait(60)
        return real(*a, **kw)

    monkeypatch.setattr(store, "save_checkpoint", held)
    svc = DatalogService(TC, {"e": _edges(seed=9)}, durable_dir=tmp_path,
                         **CAPS)
    svc.ask("tc", (1, None))
    first = svc.snapshot()
    svc.append("e", np.array([[1, 45]], np.int64))
    second = svc.snapshot()
    gate.set()
    svc._durable.wait()
    assert (first, second) == (1, 2)
    assert complete_steps(tmp_path / "snapshots") == [2, 1]
    svc.close()
