"""Crash-safe durability for the serving tier: WAL + snapshots + recovery.

The serving stack (``session.py`` and everything above it) is in-memory: a
restart used to recompute every closure from scratch, and cold start is
~100x steady state (``BENCH_serve.json``).  This module gives a
:class:`~repro.service.session.DatalogService` the durability story of a
relational system, in three layers:

* **write-ahead log** (:class:`WriteAheadLog`) — every monotone EDB append
  is framed (length + CRC32 over the payload), appended to ``wal.log`` and
  fsync'd *before* the in-memory state mutates.  Replay walks the frames
  sequentially; the first bad CRC or short read marks a torn tail, which is
  truncated (a crash mid-append loses at most the append that was in
  flight, never earlier records).  Records are COO: relation name + the
  validated ``(m, arity)`` int64 rows + the post-append epoch.

* **snapshots** — :func:`snapshot_state` flattens the hot serving state to
  a flat ``{positional-key: ndarray}`` tree (EDB spine, dense/CSR carrier
  relations via ``core.sparse.csr_to_state``, the epoch-tagged answer
  cache's raw closure rows, and the batched tuple templates' fixpoint
  snapshots) plus a JSON "meta" leaf naming everything.  The tree is
  written through the existing sharded atomic-rename checkpoint store on a
  background :class:`~repro.checkpoint.store.AsyncCheckpointer` thread, so
  snapshotting never blocks the serving path on file I/O.  Keys are purely
  positional (``db/0``, ``cache/3/raw``) because the store escapes ``/`` as
  ``__`` in npz member names — relation names like ``__qseed_tc__bf`` must
  never appear in a key.

* **recovery** (:meth:`DurabilityManager.recover`) — newest *complete*
  snapshot restored via the template-free loader, then WAL records past the
  snapshot's ``wal_seq`` replayed through the ordinary
  ``DatalogService.append`` path, which resumes cached closures with the
  existing append-resume machinery (``incremental.resume_init`` /
  ``replay_init``).  A restarted service is therefore *warm* — caches,
  carrier matrices and tuple snapshots all populated — and bit-identical to
  a twin that never restarted.

Graceful degradation, never a crash: a corrupt newest snapshot falls back
to the previous generation (the store keeps ``keep_snapshots`` of them),
then to a cold rebuild from the genesis EDB + full WAL replay.  Duplicate
WAL replay is a semantic no-op — EDB relations are sets under appends
(``np.unique``) and the additive carriers pre-filter resident arcs — so
replaying from an older-than-necessary point is safe, only slower.  The
path taken is reported in ``explain()["durability"]`` and the
``datalog_recovery_*`` / ``datalog_wal_*`` / ``datalog_snapshot_*``
metrics, with ``wal_append`` / ``snapshot`` / ``recover`` spans in the
tracer.
"""
from __future__ import annotations

import json
import os
import shutil
import struct
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from ..checkpoint.store import (AsyncCheckpointer, CheckpointCorrupt,
                                CheckpointWriteError, complete_steps,
                                load_checkpoint_raw)
from ..core import sparse as _sparse
from ..core.semiring import carrier_for
from ..obs.trace import NULL_TRACER
from . import incremental as _inc
from .cache import CacheEntry

__all__ = ["WriteAheadLog", "DurabilityManager", "WalCorrupt",
           "snapshot_state", "restore_state"]

_WAL_MAGIC = b"DWAL0001"
_WAL_HDR = struct.Struct("<II")  # (payload byte length, CRC32 of payload)


class WalCorrupt(RuntimeError):
    """A WAL frame failed validation somewhere replay cannot repair (bad
    magic).  Torn *tails* never raise — they truncate."""


def _pack_record(rel: str, rows: np.ndarray, epoch: int) -> bytes:
    rows = np.ascontiguousarray(np.asarray(rows, np.int64))
    head = json.dumps({"rel": rel, "shape": list(rows.shape),
                       "epoch": int(epoch)}).encode()
    return head + b"\n" + rows.tobytes()


def _unpack_record(payload: bytes):
    head, _, body = payload.partition(b"\n")
    meta = json.loads(head.decode())
    rows = np.frombuffer(body, np.int64).reshape(meta["shape"]).copy()
    return meta["rel"], rows, int(meta["epoch"])


class WriteAheadLog:
    """Append-only, CRC32-framed, fsync'd log of EDB appends.

    Frame layout after the 8-byte magic: ``<u32 len><u32 crc32>payload``.
    ``fsync=False`` trades the durability of the last few records for
    append latency (the OS still orders the writes); recovery semantics are
    unchanged either way."""

    def __init__(self, path: str | Path, fsync: bool = True):
        self.path = Path(path)
        self.fsync = fsync
        self.records = 0  # records currently in the file (set by replay)
        self.torn_bytes = 0  # bytes truncated off the tail at open
        existing = self.path.exists() and self.path.stat().st_size > 0
        if not existing:
            self.path.parent.mkdir(parents=True, exist_ok=True)
            with open(self.path, "wb") as f:
                f.write(_WAL_MAGIC)
                f.flush()
                os.fsync(f.fileno())
        self._f = open(self.path, "r+b")
        self._scan_and_repair()
        self._f.seek(0, os.SEEK_END)

    def _scan_and_repair(self) -> None:
        """Walk the frames; truncate at the first torn/corrupt one."""
        import zlib
        f = self._f
        f.seek(0)
        magic = f.read(len(_WAL_MAGIC))
        if magic != _WAL_MAGIC:
            raise WalCorrupt(f"{self.path}: bad WAL magic {magic!r}")
        good_end = f.tell()
        n = 0
        while True:
            hdr = f.read(_WAL_HDR.size)
            if len(hdr) < _WAL_HDR.size:
                break  # clean EOF or torn header
            length, crc = _WAL_HDR.unpack(hdr)
            payload = f.read(length)
            if len(payload) < length or (zlib.crc32(payload)
                                         & 0xFFFFFFFF) != crc:
                break  # torn tail: short payload or bit rot in the last frame
            try:
                _unpack_record(payload)
            except Exception:  # undecodable despite CRC: treat as torn
                break
            good_end = f.tell()
            n += 1
        end = f.seek(0, os.SEEK_END)
        if end > good_end:
            self.torn_bytes = end - good_end
            f.truncate(good_end)
            f.flush()
            os.fsync(f.fileno())
        self.records = n

    def append(self, rel: str, rows: np.ndarray, epoch: int) -> int:
        """Frame + append + (optionally) fsync one record; returns the
        record's sequence number (0-based position in the log)."""
        import zlib
        payload = _pack_record(rel, rows, epoch)
        frame = _WAL_HDR.pack(len(payload),
                              zlib.crc32(payload) & 0xFFFFFFFF) + payload
        self._f.write(frame)
        self._f.flush()
        if self.fsync:
            os.fsync(self._f.fileno())
        seq = self.records
        self.records += 1
        return seq

    def replay(self):
        """Yield ``(rel, rows, epoch)`` for every intact record (the torn
        tail, if any, was truncated at open)."""
        import zlib
        with open(self.path, "rb") as f:
            f.read(len(_WAL_MAGIC))
            while True:
                hdr = f.read(_WAL_HDR.size)
                if len(hdr) < _WAL_HDR.size:
                    return
                length, crc = _WAL_HDR.unpack(hdr)
                payload = f.read(length)
                if len(payload) < length or (zlib.crc32(payload)
                                             & 0xFFFFFFFF) != crc:
                    return
                yield _unpack_record(payload)

    @property
    def nbytes(self) -> int:
        return self.path.stat().st_size if self.path.exists() else 0

    def close(self) -> None:
        if not self._f.closed:
            self._f.flush()
            if self.fsync:
                os.fsync(self._f.fileno())
            self._f.close()


# ---------------------------------------------------------------------------
# Snapshot (de)construction
# ---------------------------------------------------------------------------


def _freeze(res):
    for a in res if isinstance(res, tuple) else (res,):
        a.flags.writeable = False
    return res


def snapshot_state(svc, wal_seq: int) -> dict:
    """Flatten the hot serving state to ``{positional-key: ndarray}``.

    Must run under ``svc.lock`` — the tree is a consistent cut of (EDB,
    carrier relations, answer cache, tuple snapshots) at one epoch.  Device
    arrays are synced to host here; the file I/O happens later on the
    checkpoint writer thread."""
    meta: dict = {"epoch": svc.epoch, "wal_seq": int(wal_seq),
                  "db": [], "dense": [], "cache": [], "snaps": []}
    flat: dict[str, np.ndarray] = {}
    for i, rel in enumerate(sorted(svc.db)):
        meta["db"].append(rel)
        flat[f"db/{i}"] = np.asarray(svc.db[rel])
    for i, (pred, ds) in enumerate(sorted(svc._dense.items())):
        d = {"pred": pred, "n": int(ds.n), "n_alloc": int(ds.n_alloc),
             "flips": int(ds.flips), "last_flip": ds.last_flip}
        if ds.is_csr:
            arrays, cmeta = _sparse.csr_to_state(ds.csr)
            d["repr"], d["csr_meta"] = "csr", cmeta
            for name, arr in arrays.items():
                flat[f"rel/{i}/{name}"] = np.asarray(arr)
        else:
            d["repr"] = "dense"
            flat[f"rel/{i}/matrix"] = np.asarray(ds.matrix)
        meta["dense"].append(d)
    # dense entries' raw carrier rows stack into one array per (shape,
    # dtype) group — hundreds of per-entry npz members and device puts
    # collapse to a handful (the restart cost is dominated by exactly this)
    groups: dict[tuple, list[np.ndarray]] = {}
    group_ids: dict[tuple, int] = {}
    for i, (key, ent) in enumerate(svc.cache.items()):  # oldest -> newest
        c = {"key": list(key), "kind": ent.kind, "pred": ent.pred,
             "src": ent.src, "hits": int(ent.hits)}
        if ent.kind == "dense":
            raw = np.asarray(ent.raw)
            gkey = (raw.shape, str(raw.dtype))
            g = group_ids.setdefault(gkey, len(group_ids))
            rows = groups.setdefault(gkey, [])
            c["g"], c["i"] = g, len(rows)
            rows.append(raw)
        else:
            res = ent.result
            if isinstance(res, tuple):
                c["agg"] = True
                flat[f"cache/{i}/rows"] = np.asarray(res[0])
                flat[f"cache/{i}/vals"] = np.asarray(res[1])
            else:
                c["agg"] = False
                flat[f"cache/{i}/rows"] = np.asarray(res)
        meta["cache"].append(c)
    for gkey, g in group_ids.items():
        flat[f"craw/{g}"] = np.stack(groups[gkey])
    si = 0
    for (pred, adn), tpl in sorted(svc._templates.items()):
        for skey, snap in tpl._snaps.items():
            prefix = f"snap/{si}/"
            smeta = _inc.snapshot_to_state(
                snap, lambda name, arr, p=prefix: flat.__setitem__(p + name,
                                                                   arr))
            smeta.update(pred=pred, adn=adn,
                         skey=[list(k) for k in skey])
            meta["snaps"].append(smeta)
            si += 1
    meta_bytes = json.dumps(meta).encode()
    flat["meta"] = np.frombuffer(meta_bytes, np.uint8).copy()
    return flat


def restore_state(svc, flat: dict) -> dict:
    """Inverse of :func:`snapshot_state`: rebuild the service's hot state in
    place from a loaded flat tree.  Raises :class:`CheckpointCorrupt` on any
    structural problem so the recovery ladder can fall back."""
    from .session import _DenseRelation  # late: session imports this module

    try:
        meta = json.loads(bytes(bytearray(
            np.asarray(flat["meta"], np.uint8))).decode())
    except (KeyError, ValueError) as e:
        raise CheckpointCorrupt(f"snapshot meta unreadable: {e}") from e
    try:
        # -- EDB spine (arrays were normalized by the engine before save)
        for i, rel in enumerate(meta["db"]):
            svc.db[rel] = np.asarray(flat[f"db/{i}"])
        svc._base.invalidate()
        svc.epoch = int(meta["epoch"])
        # -- carrier relations: exact representation, COO tail included
        svc._dense.clear()
        for i, d in enumerate(meta["dense"]):
            pred = d["pred"]
            low = svc._lowering(pred)
            if low is None:
                raise CheckpointCorrupt(
                    f"snapshot names a non-decomposable predicate {pred!r}")
            ds = _DenseRelation.__new__(_DenseRelation)
            ds.low = low
            ds.sr = carrier_for(low.kind)
            ds.n = int(d["n"])
            ds.n_alloc = int(d["n_alloc"])
            ds.flips = int(d["flips"])
            ds.last_flip = d["last_flip"]
            ds.tuning = None
            ds.fixpoint = dict.fromkeys(_DenseRelation.FIXPOINT_TOTALS, 0)
            if not ds.sr.idempotent:
                edges = svc.db.get(low.edb)
                ds._edges = set() if edges is None or not len(edges) else {
                    tuple(r) for r in np.unique(edges, axis=0).tolist()}
            if d["repr"] == "csr":
                prefix = f"rel/{i}/"
                arrays = {k[len(prefix):]: v for k, v in flat.items()
                          if k.startswith(prefix)}
                ds.csr = _sparse.csr_from_state(arrays, d["csr_meta"])
                ds.matrix = None
            else:
                ds.matrix = jnp.asarray(flat[f"rel/{i}/matrix"])
                ds.csr = None
            svc._dense[pred] = ds
        # -- batched tuple templates' fixpoint snapshots (template rebuilt
        #    from the persisted query literals; plan building is the cost of
        #    a cold *plan*, not a cold *fixpoint*)
        for si, smeta in enumerate(meta["snaps"]):
            prefix = f"snap/{si}/"
            snap = _inc.snapshot_from_state(
                smeta, lambda name, p=prefix: flat[p + name])
            tpl, _ = svc._template(smeta["pred"], smeta["adn"],
                                   snap.qlits[0])
            if not tpl.resumable:
                continue
            tpl._ensure_qid_engine(svc)
            skey = tuple(tuple(k) for k in smeta["skey"])
            tpl._snaps[skey] = snap
        # -- answer cache, oldest -> newest (exact LRU order); entries keep
        #    host VIEWS into the stacked raw groups — per-entry device
        #    dispatch here would dominate restart, and every consumer
        #    (jnp.stack in _refresh_dense, _format on first serve) converts
        #    lazily anyway
        svc.cache.clear()
        craw = {}
        g = 0
        while f"craw/{g}" in flat:
            craw[g] = np.asarray(flat[f"craw/{g}"])
            g += 1
        for i, c in enumerate(meta["cache"]):
            key = tuple(c["key"])
            if c["kind"] == "dense":
                # result=None defers formatting to the first hit, exactly
                # like an append-refreshed entry
                ent = CacheEntry("dense", c["pred"], None, svc.epoch,
                                 src=c["src"], raw=craw[c["g"]][c["i"]])
            else:
                rows = flat[f"cache/{i}/rows"]
                res = (rows, flat[f"cache/{i}/vals"]) if c["agg"] else rows
                ent = CacheEntry("tuple", c["pred"], _freeze(res), svc.epoch)
            ent.hits = int(c["hits"])
            svc.cache.put(key, ent)
    except CheckpointCorrupt:
        raise
    except Exception as e:  # malformed snapshot of any other stripe
        raise CheckpointCorrupt(f"snapshot restore failed: {e}") from e
    return meta


# ---------------------------------------------------------------------------
# The manager: WAL + snapshot cadence + the recovery ladder
# ---------------------------------------------------------------------------


class DurabilityManager:
    """Owns a service's durable directory: ``wal.log`` + ``snapshots/``.

    ``snapshot_every=N`` auto-snapshots after every N logged appends
    (0 = explicit ``DatalogService.snapshot()`` calls only).
    ``keep_snapshots`` bounds the generations retained — at least 2 keeps
    the degradation ladder meaningful.  ``fsync=False`` relaxes the WAL's
    per-append fsync.
    """

    def __init__(self, path: str | Path, *, snapshot_every: int = 0,
                 keep_snapshots: int = 3, n_shards: int = 2,
                 fsync: bool = True, tracer=None):
        self.dir = Path(path)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.snap_dir = self.dir / "snapshots"
        self.snapshot_every = int(snapshot_every)
        self.keep_snapshots = max(1, int(keep_snapshots))
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.wal = WriteAheadLog(self.dir / "wal.log", fsync=fsync)
        self._ckpt = AsyncCheckpointer(self.snap_dir, n_shards=n_shards)
        self._replaying = False
        self._appends_since_snap = 0
        self._last_step = 0  # the newest step handed to the writer
        self.counters = {"wal_records": 0, "wal_bytes": 0,
                         "snapshots": 0, "snapshot_errors": 0}
        #: recovery report, filled by :meth:`recover` (explain()/metrics)
        self.recovery: dict = {"mode": "fresh", "snapshot_step": None,
                               "wal_replayed": 0, "wal_skipped": 0,
                               "fallbacks": 0, "torn_bytes": 0,
                               "seconds": 0.0}

    # -- write path ----------------------------------------------------------

    def log_append(self, rel: str, rows: np.ndarray, epoch: int) -> None:
        """WAL the append BEFORE the in-memory mutation (classic
        write-ahead); no-ops during recovery replay."""
        if self._replaying:
            return
        with self.tracer.span("wal_append", cat="durable", rel=rel,
                              rows=int(len(rows))):
            self.wal.append(rel, rows, epoch)
        self.counters["wal_records"] += 1
        self.counters["wal_bytes"] = self.wal.nbytes

    def maybe_snapshot(self, svc) -> None:
        """Auto-snapshot cadence hook, called at the end of every append."""
        if self._replaying or self.snapshot_every <= 0:
            return
        self._appends_since_snap += 1
        if self._appends_since_snap >= self.snapshot_every:
            self.snapshot(svc)

    def snapshot(self, svc) -> int | None:
        """Build a consistent snapshot tree (caller holds ``svc.lock``) and
        hand it to the background checkpoint writer; returns the step, or
        None when the previous background save failed (reported once via
        ``datalog_snapshot_errors``, then the writer recovers)."""
        with self.tracer.span("snapshot", cat="durable", epoch=svc.epoch):
            flat = snapshot_state(svc, self.wal.records)
            steps = complete_steps(self.snap_dir)
            # the writer may not have published the step handed to it last:
            # numbering from the disk alone would hand it the same step again
            step = max(steps[0] if steps else 0, self._last_step) + 1
            try:
                self._ckpt.save(step, flat)
            except CheckpointWriteError:
                self.counters["snapshot_errors"] += 1
                return None
            self._last_step = step
            self.counters["snapshots"] += 1
            self._appends_since_snap = 0
            self._prune(keep_from=step)
            return step

    def wait(self) -> None:
        """Block until the in-flight snapshot (if any) is published;
        re-raises a background :class:`CheckpointWriteError` once."""
        self._ckpt.wait()

    def _prune(self, keep_from: int) -> None:
        """Drop generations beyond ``keep_snapshots``, counting the step
        ``keep_from`` just handed to the writer as the newest whether or not
        the writer has published it yet."""
        older = [s for s in complete_steps(self.snap_dir) if s < keep_from]
        for step in older[self.keep_snapshots - 1:]:
            shutil.rmtree(self.snap_dir / f"step_{step:08d}",
                          ignore_errors=True)

    # -- recovery ------------------------------------------------------------

    def recover(self, svc) -> dict:
        """The degradation ladder: newest complete snapshot -> older
        generations -> cold rebuild from the genesis EDB; then WAL replay
        through the ordinary append/resume path.  Never raises for data
        faults — the report records what happened."""
        t0 = time.monotonic()
        rep = self.recovery
        rep["torn_bytes"] = self.wal.torn_bytes
        with self.tracer.span("recover", cat="durable"):
            steps = complete_steps(self.snap_dir)
            wal_from = 0
            restored = None
            for gen, step in enumerate(steps):
                try:
                    flat, _ = load_checkpoint_raw(self.snap_dir, step=step)
                    meta = restore_state(svc, flat)
                except CheckpointCorrupt:
                    rep["fallbacks"] += 1
                    continue
                restored = (step, gen, meta)
                break
            if restored is not None:
                step, gen, meta = restored
                rep["mode"] = "degraded" if gen else "warm"
                rep["snapshot_step"] = step
                wal_from = int(meta["wal_seq"])
            elif self.wal.records or steps:
                rep["mode"] = "cold"  # genesis EDB + full WAL replay
            else:
                rep["mode"] = "fresh"  # empty directory: nothing to recover
            self._replaying = True
            try:
                for seq, (rel, rows, _epoch) in enumerate(self.wal.replay()):
                    if seq < wal_from:
                        continue
                    try:
                        svc.append(rel, rows)
                        rep["wal_replayed"] += 1
                    except Exception:  # noqa: BLE001 — degrade, don't die
                        rep["wal_skipped"] += 1
            finally:
                self._replaying = False
        rep["seconds"] = time.monotonic() - t0
        return rep

    # -- introspection -------------------------------------------------------

    def report(self) -> dict:
        """The ``explain()["durability"]`` section."""
        return {
            "dir": str(self.dir),
            "wal": {"records": self.wal.records, "bytes": self.wal.nbytes,
                    "fsync": self.wal.fsync},
            "snapshots": {"written": self.counters["snapshots"],
                          "errors": self.counters["snapshot_errors"],
                          "every": self.snapshot_every,
                          "keep": self.keep_snapshots,
                          "steps": complete_steps(self.snap_dir)},
            "recovery": dict(self.recovery),
        }

    def absorb_metrics(self, m) -> None:
        """Collector for the unified registry (``datalog_recovery_*`` and
        friends); registered by the owning service."""
        m.counter("datalog_wal_records_total",
                  "EDB appends written to the WAL").set(
            self.counters["wal_records"])
        m.gauge("datalog_wal_bytes", "WAL file size").set(self.wal.nbytes)
        m.counter("datalog_snapshots_total",
                  "serving-state snapshots handed to the background writer"
                  ).set(self.counters["snapshots"])
        m.counter("datalog_snapshot_errors_total",
                  "background snapshot saves that failed").set(
            self.counters["snapshot_errors"])
        rec = self.recovery
        c = m.counter("datalog_recovery_total",
                      "service recoveries at startup, by degradation mode")
        for mode in ("warm", "degraded", "cold"):
            c.set(1 if rec["mode"] == mode else 0, {"mode": mode})
        m.counter("datalog_recovery_wal_replayed_total",
                  "WAL records replayed through append-resume at recovery"
                  ).set(rec["wal_replayed"])
        m.counter("datalog_recovery_fallbacks_total",
                  "snapshot generations skipped as corrupt at recovery").set(
            rec["fallbacks"])
        m.gauge("datalog_recovery_seconds",
                "wall time of the last recovery").set(rec["seconds"])

    def close(self) -> None:
        try:
            self._ckpt.close()
        except CheckpointWriteError:
            self.counters["snapshot_errors"] += 1
        self.wal.close()
