"""``DatalogService`` — load a program + EDB once, answer query streams fast.

``Engine.ask()`` is built for one-shot queries: every call re-runs the magic
rewrite, re-plans, and evaluates a solo fixpoint.  A service amortizes all of
that across the stream:

* **plan/template memoization** — the magic rewrite and compiled plan for a
  query *shape* (predicate + adornment) build once: the seed constants are
  moved out of the rewritten program into a tiny seed EDB relation
  (``m__tc__bf(X) <- __qseed(X)``), so every ``tc(c, _)`` query shares one
  plan and — via the engine's structurally-keyed runner cache — one compiled
  fixpoint.  Repeat query shapes never re-plan or re-trace.
* **micro-batched dense fixpoints** — B concurrent single-source queries on
  a decomposable predicate coalesce into one (B, n) frontier fixpoint
  (``batch.py``); one ⊕.⊗ product per iteration serves the whole batch, and
  a device mesh shards the batch rows Fig.-4 style.
* **result caching** — an LRU of whole answers (``cache.py``) keyed by the
  query constants, epoch-tagged.
* **incremental appends** — monotone EDB appends resume cached dense
  closures from the new-fact delta frontier (``incremental.py``) instead of
  recomputing, and invalidate only what they must.

    svc = DatalogService(TC, db={"arc": edges})
    svc.ask("tc", (1, None))                  # cold: plan + fixpoint
    svc.ask_batch([("tc", (s, None)) for s in sources])   # one fixpoint
    svc.append("arc", [[7, 8]])               # resume, don't recompute
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from ..core import sparse as _sparse

from ..core.engine import (CapacityError, Engine, as_query_literal,
                           fixpoint_trace_count, query_row_mask,
                           split_qid_answers)
from ..core.ir import Const, Literal, Program, Rule, Var, fresh_var
from ..core.magic import (BOUND, FrontierLowering, MagicError, agg_positions,
                          attribute_qids, detect_frontier_lowering,
                          frontier_query_source, qid_batchable,
                          query_adornment)
from ..core.magic import rewrite as magic_rewrite
from ..core.parser import parse_program
from ..core.planner import PlanError, demanded_strata
from ..core.semiring import MIN_PLUS, carrier_for, edge_arity
from ..obs.metrics import NULL_METRICS, MetricsRegistry
from ..obs.trace import NULL_TRACER, Tracer
from . import batch as _batch
from . import incremental as _inc
from .cache import CacheEntry, LRUCache

#: batch-size histogram buckets (queries per launched batch)
_BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)


@dataclasses.dataclass
class ServiceStats:
    """Evaluation-side counters; result-cache hit/miss counters live on the
    :class:`~repro.service.cache.LRUCache` itself (``service.cache.hits``)."""

    plans_built: int = 0  # templates constructed (magic rewrite + plan)
    plan_hits: int = 0  # queries served by a memoized template
    tuple_runs: int = 0  # PSN evaluations (template engine runs)
    dense_fixpoints: int = 0  # batched closure fixpoints launched (any repr)
    csr_fixpoints: int = 0  # ... of which ran the CSR-packed sparse engine
    batched_queries: int = 0  # queries answered by those fixpoints
    tuple_fixpoints: int = 0  # qid-batched tuple fixpoints launched
    tuple_batched_queries: int = 0  # queries answered by those fixpoints
    appends: int = 0
    resumed_rows: int = 0  # cached closures refreshed by append-resume
    resumed_tuple_rows: int = 0  # tuple answers refreshed by snapshot resume
    dropped_cold: int = 0  # cold entries evicted instead of resumed


@dataclasses.dataclass
class _PendingBatch:
    """In-flight state between :meth:`DatalogService.launch_batch` and
    :meth:`DatalogService.finalize_batch` — the double-buffering unit of the
    admission front-end.  Holds the device results (lazy jax arrays: the
    fixpoint may still be running when launch returns) plus everything the
    host-side finalize needs without re-touching shared engine state."""

    epoch: int  # service epoch at launch; finalize asserts it is unchanged
    qlits: list
    out: list  # answer slots; EDB selections fill at launch
    #: the flush's number in the trace (``flush=`` on its spans); None when
    #: the tracer is off
    seq: int | None = None
    hits: list = dataclasses.field(default_factory=list)  # (slot, CacheEntry)
    #: [(pred, _DenseRelation, items, uniq_srcs, in_range, DenseResult|None,
    #:   launch_meta)] — launch_meta carries the launch timestamp, which
    #: ``datalog_device_seconds`` reads at device sync
    dense: list = dataclasses.field(default_factory=list)
    #: [(pred, items, uniq, (template, launched)|None, results|None)]
    tuples: list = dataclasses.field(default_factory=list)


def _freeze(res):
    """Mark a cached answer's arrays read-only: cache hits (and duplicate
    queries in one batch) hand out the SAME arrays, so a caller mutating an
    answer must fail loudly instead of corrupting every later hit."""
    for a in res if isinstance(res, tuple) else (res,):
        a.flags.writeable = False
    return res


class _DenseRelation:
    """Carrier state for one decomposable predicate: dense matrix OR CSR.

    The base relation packs once per service (``Engine.ask_dense`` rebuilds
    per call) and is maintained under appends.  Representation is decided at
    (re)build time: ``svc.sparse`` forces one, ``None`` lets the density
    heuristic pick — below ``sparse_threshold`` the CSR-packed segment
    engine (``core.sparse``, O(|E|) per iteration) replaces the
    ``n_align``-rounded O(n²) matrix behind the *same* batching interface
    (:meth:`seed_rows` / :meth:`run_batch`).

    Appends: the dense matrix scatters arcs in place; the CSR appends them
    to its COO tail, folding into the spine at a rebuild threshold.  Either
    way ``n_alloc`` rounds the live domain up to ``n_align`` so small domain
    growth keeps the compiled fixpoint shapes stable; outgrowing it rebuilds
    (re-running the heuristic — density drifts as graphs grow).
    """

    #: running totals of the serving flushes' fixpoint counters, kept in
    #: ``self.fixpoint`` (``explain()["relations"][pred]["fixpoint"]``)
    FIXPOINT_TOTALS = ("launches", "rows", "pad_rows", "iterations",
                       "row_iterations", "active_rows", "delta_facts")

    def __init__(self, svc: "DatalogService", low: FrontierLowering):
        self.low = low
        # the carrier-misrouting bug lived here: `BOOL if bool else MIN_PLUS`
        # silently ran max-plus and plus-times lowerings on the min-plus
        # semiring.  Route through the typed table instead.
        self.sr = carrier_for(low.kind)
        self.n = 0
        self.n_alloc = 0
        self.matrix = None
        self.csr = None
        self.flips = 0  # representation changes across rebuilds (live
        self.last_flip: str | None = None  # density heuristic, ROADMAP 6c)
        self.tuning: dict | None = None  # autotuner report (tune= on)
        self.fixpoint = dict.fromkeys(self.FIXPOINT_TOTALS, 0)
        self._rebuild(svc)

    @property
    def is_csr(self) -> bool:
        return self.csr is not None

    def _rebuild(self, svc: "DatalogService"):
        prev = None if (self.matrix is None and self.csr is None) else \
            ("csr" if self.is_csr else "dense")
        arity = edge_arity(self.low.kind)
        edges = svc.db.get(self.low.edb, np.zeros((0, arity), np.int64))
        if not self.sr.idempotent:
            # additive ⊕ is set-semantics over arcs: exact duplicate facts
            # collapse before they can double-count, and the distinct-arc
            # set filters appends so the increment replay's Δ-disjointness
            # invariant (only genuinely-new arcs re-derive) holds
            if len(edges):
                edges = np.unique(edges, axis=0)
            self._edges = {tuple(r) for r in edges.tolist()}
        n = int(edges[:, :2].max()) + 1 if len(edges) else 0
        align = svc.n_align
        self.n = n
        self.n_alloc = max(((n + align - 1) // align) * align, align)
        use_csr = svc.sparse
        if use_csr is None:
            # density over the LIVE domain (the same |E|/n² cut as
            # Engine.ask_dense), not the align-padded allocation
            use_csr = _sparse.prefer_csr(len(edges), n, svc.sparse_threshold)
        if use_csr:
            self.matrix = None
            cfg = svc._tuned_config(self, edges)
            if cfg is None:
                self.csr = _sparse.build_csr(edges, self.n_alloc,
                                             self.low.kind)
            else:
                from ..kernels import autotune as _at
                self.csr = _at.build_tuned(edges, self.n_alloc,
                                           self.low.kind, cfg)
        elif self.low.kind == "bool":
            self.csr = None
            adj = np.zeros((self.n_alloc, self.n_alloc), bool)
            if len(edges):
                adj[edges[:, 0], edges[:, 1]] = True
            self.matrix = jnp.asarray(adj)
        else:
            # weighted dense matrix in the carrier: ⊕-zero fill (+inf for
            # min-plus, -inf for max-plus, 0 for plus-times) and the ⊕
            # scatter folds parallel arcs (min/max for the idempotent
            # carriers; additive arcs already deduped above, so += sums
            # distinct parallel arcs exactly once each)
            self.csr = None
            w = np.full((self.n_alloc, self.n_alloc), self.sr.zero, np.float32)
            if len(edges):
                scatter = (np.minimum if self.sr is MIN_PLUS
                           else np.maximum if self.sr.idempotent else np.add)
                scatter.at(w, (edges[:, 0], edges[:, 1]),
                           edges[:, 2].astype(np.float32))
            self.matrix = jnp.asarray(w)
        now = "csr" if use_csr else "dense"
        if prev is not None and prev != now:
            self.flips += 1
            self.last_flip = f"{prev}->{now}"

    def seed_rows(self, srcs, pads: tuple[int, ...]) -> jnp.ndarray:
        """The (B, n_alloc) frontier rows ``A[srcs]`` in the carrier.  A CSR
        seed is built at the padded batch width, so it reuses the seed
        program the cold batches of that width compiled."""
        if self.is_csr:
            b = len(srcs)
            idx = list(srcs) + [srcs[0]] * (_batch.pad_batch_size(b, pads) - b)
            return _sparse.rows_from_sources(self.csr, idx)[:b]
        return self.matrix[jnp.asarray(srcs)]

    def run_batch(self, svc: "DatalogService", srcs: list[int], init=None):
        """One batched frontier fixpoint over this relation's representation
        (``init`` overrides the seed — append-resume)."""
        if self.is_csr:
            return _batch.run_frontier_batch_csr(
                self.csr, srcs, svc.batch_pads,
                spmv=svc._spmv(self.low.kind, self.csr),
                mesh=svc.mesh, init=init)
        return _batch.run_frontier_batch(
            self.sr, self.matrix, srcs, svc.batch_pads,
            matmul=svc._matmul(self.sr), mesh=svc.mesh, init=init)

    def tally(self, rows: int, pad: int, iterations: int, active_rows: int,
              delta_facts: int) -> None:
        """Add one serving flush's fixpoint counters to the totals; a
        counter the loop did not count (-1, the sharded path) is left
        out."""
        t = self.fixpoint
        t["launches"] += 1
        t["rows"] += rows
        t["pad_rows"] += pad
        t["iterations"] += iterations
        t["row_iterations"] += pad * iterations
        if active_rows >= 0:
            t["active_rows"] += active_rows
        if delta_facts >= 0:
            t["delta_facts"] += delta_facts

    def append(self, svc: "DatalogService", rows: np.ndarray) -> bool:
        """Fold appended arcs in; returns True when the domain outgrew the
        allocation (a rebuild — cached rows need re-padding).

        For additive carriers the rows are first filtered down to the
        *genuinely new* arcs (set semantics: exact duplicates of resident
        facts re-derive nothing) and the filtered Δ lands on
        :attr:`last_delta` — the increment-replay seed of
        ``DatalogService._refresh_dense`` depends on Δ being disjoint from
        the pre-append arc set."""
        if not self.sr.idempotent:
            rows = self._new_arcs(rows)
        self.last_delta = rows
        new_n = max(self.n, int(rows[:, :2].max()) + 1 if len(rows) else 0)
        if new_n > self.n_alloc:
            self._rebuild(svc)  # svc.db already holds the appended relation
            return True
        self.n = new_n
        if not self.sr.idempotent:
            self._edges.update(map(tuple, rows.tolist()))
        if len(rows):
            if self.is_csr:
                if _sparse.tail_will_rebuild(self.csr, len(rows),
                                             svc.csr_rebuild_frac):
                    # the tail outgrew the spine: fold via a FULL rebuild —
                    # which re-runs the density heuristic, so a tail that
                    # densified the graph past the threshold flips the
                    # carrier back to the dense matrix (live flip-back)
                    # instead of unconditionally re-packing CSR
                    self._rebuild(svc)
                else:
                    self.csr = _sparse.csr_append(self.csr, rows,
                                                  svc.csr_rebuild_frac)
            elif self.low.kind == "bool":
                self.matrix = self.matrix.at[rows[:, 0], rows[:, 1]].set(True)
            else:
                vals = jnp.asarray(rows[:, 2], jnp.float32)
                at = self.matrix.at[rows[:, 0], rows[:, 1]]
                self.matrix = (at.min(vals) if self.sr is MIN_PLUS
                               else at.max(vals) if self.sr.idempotent
                               else at.add(vals))
        return False

    def _new_arcs(self, rows: np.ndarray) -> np.ndarray:
        """Set-semantics append filter for additive carriers: collapse exact
        duplicates within the batch, then drop arcs already resident."""
        if not len(rows):
            return np.asarray(rows, np.int64).reshape(0, 3)
        uniq = np.unique(np.asarray(rows, np.int64), axis=0)
        keep = [tuple(r) not in self._edges for r in uniq.tolist()]
        return uniq[np.asarray(keep, bool)]


class _QueryTemplate:
    """Memoized evaluation template for one (predicate, adornment) shape.

    ``mode='magic'``: the magic-rewritten program with the seed fact swapped
    for a seed-EDB rule; per query only the seed rows change, so the plan and
    (via the engine's runner cache) the compiled fixpoints are reused.

    ``mode='demand'``: fallback when the magic program cannot plan (cartesian
    magic prefixes, PreM violations through magic cycles — mirroring
    ``Engine._query_engine``).  The demanded-strata model is constant-free,
    so it evaluates once and every query of the shape post-filters it.
    """

    def __init__(self, svc: "DatalogService", q: Literal, adn: str):
        self.pred = q.pred
        self.adn = adn
        self.bound_positions = [i for i, c in enumerate(adn) if c == BOUND]
        self.seed_rel = f"__qseed_{q.pred}__{adn}"
        self._model_fresh = False
        self._mr = None
        self._qid_engine: Engine | None = None
        #: LRU of the last K batches' fixpoint snapshots (K =
        #: ``DatalogService(snapshot_lru=...)``) keyed by the batch's query
        #: cache keys — several hot batches stay append-resumable, not just
        #: the most recent one
        self._snaps: "OrderedDict[tuple, _inc.TupleSnapshot]" = OrderedDict()
        self._eng_kw = eng_kw = dict(bits=svc.bits, default_cap=svc.default_cap,
                                     join_cap=svc.join_cap,
                                     max_iters=svc.max_iters,
                                     bucket_floors=svc.bucket_floors)
        try:
            mr = magic_rewrite(svc.program, q)
            caps = dict(svc.caps)
            for name, orig in mr.aliases.items():
                if orig in svc.caps:
                    caps.setdefault(name, svc.caps[orig])
            self._caps = caps
            db = dict(svc.db)
            if mr.seed_rule is not None:
                db[self.seed_rel] = np.zeros((1, len(self.bound_positions)),
                                             np.int64)
            self.mode = "magic"
            self._mr = mr
            self.result_pred = mr.query_pred
            self.engine = Engine(self._parameterize(mr), db=db, caps=caps,
                                 **eng_kw)
        except (MagicError, PlanError):
            self.mode = "demand"
            self.result_pred = q.pred
            self.engine = Engine(demanded_strata(svc.program, q.pred),
                                 db=dict(svc.db), caps=dict(svc.caps), **eng_kw)
        #: EDB relations this template's (rewritten) program actually reads —
        #: appends to anything else leave its answers untouched
        self.reads = set(self.engine.source_program.edb_predicates())
        #: can run_batch coalesce B queries of this shape into one qid-tagged
        #: fixpoint?  Needs the magic mode and a demand-flow-complete rewrite.
        self.batchable = self.mode == "magic" and qid_batchable(self._mr)
        #: is warm-start resumption of the batched fixpoint sound under
        #: monotone appends?  (no negation, no additive aggregates)
        self.resumable = (self.batchable
                          and _inc.resumable_program(self._mr.program))

    def _parameterize(self, mr) -> Program:
        rules, dropped = [], False
        for r in mr.program.rules:
            if not dropped and r is mr.seed_rule:
                dropped = True
                continue
            rules.append(r)
        if mr.seed_rule is not None:
            vs = tuple(fresh_var("_s") for _ in mr.seed_rule.head.args)
            rules.append(Rule(Literal(mr.seed_rule.head.pred, vs),
                              (Literal(self.seed_rel, vs),)))
        return Program(rules)

    def run(self, svc: "DatalogService", q: Literal):
        eng = self.engine
        if self.mode == "demand" or not self.bound_positions:
            # constant-free evaluation: the model answers every query of the
            # shape — evaluate once per epoch, post-filter per query
            if not self._model_fresh:
                eng.invalidate().run()
                self._model_fresh = True
            return self._filter(q)
        consts = [[int(q.args[i].value) for i in self.bound_positions]]
        eng.db[self.seed_rel] = np.asarray(consts, np.int64)
        eng.invalidate(self.seed_rel).run()
        return self._filter(q)

    def _filter(self, q: Literal):
        """Restrict the evaluated model to the query goal — bound-position
        constants included (the demanded set may exceed the queried set) and
        repeated-variable equalities (``tc(X, X)``)."""
        eng = self.engine
        rows, vals = eng.materialized[self.result_pred]
        info = eng._pred_info[self.result_pred]
        mask = query_row_mask(q, rows, vals, info)
        if info.is_agg:
            return rows[mask], vals[mask]
        return rows[mask]

    # -- qid-batched evaluation ---------------------------------------------

    def _ensure_qid_engine(self, svc: "DatalogService") -> Engine:
        """Build (once) the batched twin: the same magic rewrite with a
        query-id column threaded through (``magic.attribute_qids``) and the
        seed EDB widened to (qid, consts..) rows.  Seed row counts quantize
        to power-of-two buckets inside the engine, so warm batch *sizes*
        reuse compiled fixpoints."""
        if self._qid_engine is None:
            prog = attribute_qids(self._mr, seed_rel=self.seed_rel)
            db = dict(svc.db)
            db[self.seed_rel] = np.zeros(
                (1, 1 + len(self.bound_positions)), np.int64)
            self._qid_engine = Engine(prog.program, db=db, caps=self._caps,
                                      **self._eng_kw)
        return self._qid_engine

    def run_batch(self, svc: "DatalogService", qlits: list[Literal]) -> list:
        """Evaluate B same-shape queries as ONE tuple-path fixpoint; returns
        per-query answers in order.  Raises (PlanError/CapacityError/
        ValueError) when the batch cannot run batched — callers fall back to
        sequential ``run``."""
        return self.finalize_launched(svc, self.launch_batch(svc, qlits))

    def launch_batch(self, svc: "DatalogService", qlits: list[Literal]) -> dict:
        """Device half of :meth:`run_batch`: seed + run the qid-tagged
        fixpoint and *capture* the materialized model.  The capture matters:
        the admission front-end launches the next flush on this template
        while the previous flush's host-side split is still running, and a
        second ``eng.run()`` would overwrite the engine state."""
        eng = self._ensure_qid_engine(svc)
        seeds = np.asarray(
            [[qid] + [int(q.args[i].value) for i in self.bound_positions]
             for qid, q in enumerate(qlits)], np.int64)
        eng.db[self.seed_rel] = seeds
        eng.invalidate(self.seed_rel)
        eng.run()
        return dict(seeds=seeds, qlits=list(qlits),
                    model=eng.materialized[self.result_pred],
                    info=eng._pred_info[self.result_pred],
                    state=dict(eng.materialized))

    def finalize_launched(self, svc: "DatalogService", launched: dict) -> list:
        """Host half of :meth:`run_batch`: per-qid attribution over the
        captured model + snapshot store — pure host work over the launch's
        own arrays, safe to overlap with the next flush's device fixpoint."""
        rows, vals = launched["model"]
        qlits = launched["qlits"]
        out = split_qid_answers(self.result_pred, rows, vals,
                                launched["info"], qlits)
        if self.resumable and svc.snapshot_lru > 0:
            self._store_snap(svc, tuple(svc._cache_key(q) for q in qlits),
                             _inc.TupleSnapshot(seeds=launched["seeds"],
                                                qlits=qlits,
                                                state=launched["state"]))
        return out

    def _store_snap(self, svc: "DatalogService", key: tuple,
                    snap: _inc.TupleSnapshot) -> None:
        with svc.lock:  # finalize may run off the service lock (admission)
            self._snaps[key] = snap
            self._snaps.move_to_end(key)
            while len(self._snaps) > svc.snapshot_lru:
                self._snaps.popitem(last=False)

    def _split(self, eng: Engine, qlits: list[Literal], qids=None) -> list:
        """Per-seed attribution (``engine.split_qid_answers``): the qid
        column selects the query, then the query's own constants / repeated
        variables filter (same semantics as ``_filter``)."""
        rows, vals = eng.materialized[self.result_pred]
        info = eng._pred_info[self.result_pred]
        return split_qid_answers(self.result_pred, rows, vals, info, qlits,
                                 qids=qids)

    def resume_batch(self, svc: "DatalogService", snap_key: tuple,
                     keep: list[int] | None = None) -> list | None:
        """Re-run one snapshotted batch warm-started from its fixpoint state
        (same seeds, post-append EDB); returns [(qlit, answer)] for the cache
        refresh, or None when there is nothing to resume.

        ``keep`` restricts the resume to those snapshot positions (the
        eviction-aware policy's hot entries): cold seeds and their warm rows
        are filtered OUT of the re-entered fixpoint and the new snapshot, so
        future appends never pay their demand propagation again.
        """
        snap = self._snaps.get(snap_key)
        if snap is None or not self.resumable:
            return None
        idx = list(range(len(snap.qlits))) if keep is None else sorted(keep)
        seeds = snap.seeds[idx]
        qids = [int(q) for q in seeds[:, 0]]  # original tags, non-contiguous
        qlits = [snap.qlits[i] for i in idx]
        state = snap.state
        if len(idx) < len(snap.qlits):
            state = {}
            for p, (rows, vals) in snap.state.items():
                m = np.isin(rows[:, 0], qids)
                state[p] = (rows[m], vals[m] if vals is not None else None)
        eng = self._qid_engine
        eng.db[self.seed_rel] = seeds
        eng.invalidate(self.seed_rel)
        eng.run(warm=state)
        out = self._split(eng, qlits, qids=qids)
        self._snaps[snap_key] = _inc.TupleSnapshot(
            seeds=seeds, qlits=qlits, state=dict(eng.materialized))
        return list(zip(qlits, out))

    def on_append(self, svc: "DatalogService", rel: str):
        for eng in (self.engine, self._qid_engine):
            if eng is None or rel not in eng.db:
                continue
            eng.db[rel] = svc.db[rel]
            eng.invalidate(rel)
        self._model_fresh = False
        if not self.resumable:
            self._snaps.clear()


class DatalogService:
    """A resident Datalog query server over one program + EDB.

    Parameters mirror :class:`Engine`; additionally:

    ``result_cache``  LRU capacity for whole-answer caching (0 disables).
    ``matmul``        dense-contraction override: ``None`` (jnp reference),
                      ``'pallas'`` (the tiled kernels in ``repro.kernels``),
                      or any ``(B, n) x (n, n)`` callable.
    ``mesh``          a jax device mesh — micro-batches shard their frontier
                      rows across it (the Fig.-4 decomposable plan).
    ``batch_pads``    batch-size quantization levels; padded batches reuse
                      already-compiled fixpoint shapes.
    ``n_align``       dense domain-size alignment (appends that stay under
                      the allocation keep compiled shapes stable).
    ``resume_min_hits``  eviction-aware append resume: cached entries that
                      served fewer than this many queries since their last
                      (re)compute are *dropped* on append instead of
                      resumed (0 = resume everything, the maintenance-free
                      default).
    ``resume_max_bytes``  byte-budget complement to ``resume_min_hits``:
                      per maintenance pass, entries resume hottest-first
                      until their cumulative resident bytes exceed the
                      budget; the oversized tail is dropped (0 = no budget).
    ``sparse``        closure representation for decomposable predicates:
                      True forces the CSR-packed O(|E|)-per-iteration
                      engine, False forces the dense matrix, None (default)
                      picks per relation by density (< ``sparse_threshold``
                      -> CSR).
    ``sparse_threshold``  the heuristic's |E|/n² cut (None = library
                      default, ``core.sparse.DEFAULT_SPARSE_THRESHOLD``).
    ``csr_rebuild_frac``  appended arcs fold from the CSR's COO tail into
                      the spine when the tail outgrows this fraction of it.
    ``snapshot_lru``  batched tuple templates keep their last K batches'
                      fixpoint snapshots append-resumable (1 = the
                      last-batch-only legacy behavior; 0 disables).
    ``bucket_floors`` per-relation ``quantize_rows`` floors threaded into
                      every engine (see ``benchmarks/bench_buckets.py``).
    ``tune``          kernel tuning for CSR relations
                      (``kernels.autotune``): ``True`` runs the
                      roofline-steered measured search at every relation
                      (re)build (cached per graph-shape signature), a
                      pinned :class:`~repro.kernels.autotune.KernelConfig`
                      applies without measuring, ``None``/``False`` (the
                      default) keeps the library layout.
                      ``explain()["kernels"]["tuning"]`` reports the chosen
                      config and its measured gain per predicate.
    ``metrics``       unified metrics registry (``obs.metrics``): ``None``/
                      ``True`` creates one (the default-on path, per-batch
                      observes only), ``False`` disables (NullMetrics — the
                      overhead-guard baseline), or pass a shared
                      ``MetricsRegistry``.
    ``tracer``        span tracer (``obs.trace``): ``None``/``False`` is the
                      no-op ``NULL_TRACER``, ``True`` creates a recording
                      ``Tracer``, or pass one (``svc.tracer.export_chrome``
                      writes the timeline).  Each flush is numbered: its
                      ``launch_batch``, ``finalize_batch`` and
                      ``device_sync`` spans carry ``flush=<seq>``,
                      ``launch_batch`` also ``ahead`` (flushes launched and
                      not yet synced at its start), and ``device_sync`` the
                      fixpoint's counters (``rows``, ``pad``,
                      ``iterations``, ``active_rows``, ``delta_facts``).
    ``durable_dir``   crash-safe persistence root (``service/durable.py``):
                      every append WALs before mutating, :meth:`snapshot`
                      persists the hot state through the background
                      checkpoint writer, and construction *recovers* —
                      newest complete snapshot + WAL replay through the
                      append-resume path, falling back per the degradation
                      ladder (older generation -> cold rebuild), never
                      crashing.  ``explain()["durability"]`` reports the
                      path taken.  ``None`` (default) = in-memory only.
    ``snapshot_every``  auto-snapshot after every N WALed appends
                      (0 = explicit :meth:`snapshot` calls only).
    ``keep_snapshots``  snapshot generations retained for the fallback
                      ladder (older ones are pruned after each publish).
    ``durable_fsync``  fsync the WAL per append (True); False trades the
                      tail's durability for append latency.
    """

    def __init__(self, program, db: dict[str, np.ndarray], *, bits: int = 18,
                 caps: dict[str, int] | None = None, default_cap: int = 1 << 16,
                 join_cap: int | None = None, max_iters: int = 1 << 16,
                 constants: dict[str, int] | None = None,
                 result_cache: int = 1024, matmul=None, mesh=None,
                 batch_pads: tuple[int, ...] = (1, 8, 32, 128),
                 n_align: int = 128, resume_min_hits: int = 0,
                 resume_max_bytes: int = 0, sparse: bool | None = None,
                 sparse_threshold: float | None = None,
                 csr_rebuild_frac: float = 0.25, snapshot_lru: int = 1,
                 bucket_floors: dict[str, int] | None = None,
                 tune=None, metrics=None, tracer=None,
                 durable_dir=None, snapshot_every: int = 0,
                 keep_snapshots: int = 3, durable_fsync: bool = True):
        if isinstance(program, str):
            program = parse_program(program, constants=constants)
        self.program = program
        self.bits = bits
        self.caps = dict(caps or {})
        self.default_cap = default_cap
        self.join_cap = join_cap
        self.max_iters = max_iters
        self.mesh = mesh
        self.batch_pads = tuple(batch_pads)
        self.n_align = n_align
        self.resume_min_hits = resume_min_hits
        self.resume_max_bytes = resume_max_bytes
        self.sparse = sparse
        self.sparse_threshold = (sparse_threshold
                                 if sparse_threshold is not None
                                 else _sparse.DEFAULT_SPARSE_THRESHOLD)
        self.csr_rebuild_frac = csr_rebuild_frac
        self.snapshot_lru = snapshot_lru
        self.bucket_floors = dict(bucket_floors or {})
        self.tune = tune
        self._matmul_opt = matmul
        # the base engine owns db normalization + domain validation; sharing
        # its dict means appends propagate without copying
        self._base = Engine(program, db=db, bits=bits, caps=self.caps,
                            default_cap=default_cap, join_cap=join_cap,
                            max_iters=max_iters,
                            bucket_floors=self.bucket_floors)
        self.db = self._base.db
        self.epoch = 0
        self.stats = ServiceStats()
        self.cache = LRUCache(result_cache)
        self._templates: dict[tuple[str, str], _QueryTemplate] = {}
        self._dense: dict[str, _DenseRelation] = {}
        self._lowerings: dict[str, FrontierLowering | None] = {}
        #: guards all shared serving state (cache, stats, templates, carrier
        #: relations, epoch).  Re-entrant and uncontended in single-threaded
        #: use; the admission front-end (``admission.py``) launches flushes,
        #: finalizes them and probes the cache from different threads.
        self.lock = threading.RLock()
        # -- observability (obs/): tracer, metrics -----------------------------
        if tracer is None or tracer is False:
            self.tracer = NULL_TRACER
        elif tracer is True:
            self.tracer = Tracer()
        else:
            self.tracer = tracer
        self._flush_seq = 0  # flushes numbered (tracer on only)
        self._unsynced = 0  # of those, launched and not yet synced
        if metrics is False:
            self.metrics = NULL_METRICS
        elif metrics is None or metrics is True:
            self.metrics = MetricsRegistry()
        else:
            self.metrics = metrics
        self._h_device = self.metrics.histogram(
            "datalog_device_seconds",
            "launch to device-sync wall time per batched fixpoint")
        self._h_finalize = self.metrics.histogram(
            "datalog_finalize_seconds",
            "host-side split/format/cache-fill time per finalized batch")
        self._h_batch = self.metrics.histogram(
            "datalog_batch_size", "queries per launched batch",
            buckets=_BATCH_BUCKETS)
        self.metrics.register_collector(self._absorb_stats)
        # -- durability (service/durable.py): WAL + snapshots + recovery -----
        self._durable = None
        if durable_dir is not None:
            from .durable import DurabilityManager
            self._durable = DurabilityManager(
                durable_dir, snapshot_every=snapshot_every,
                keep_snapshots=keep_snapshots, fsync=durable_fsync,
                tracer=self.tracer)
            self.metrics.register_collector(self._durable.absorb_metrics)
            with self.lock:
                self._durable.recover(self)

    # -- queries -------------------------------------------------------------

    def ask(self, pred, args: tuple | None = None):
        """Answer one query (``Engine.ask`` forms).  Equivalent to a batch of
        one — same caches, same compiled fixpoints."""
        return self.ask_batch([pred if args is None else (pred, args)])[0]

    def ask_batch(self, queries: list) -> list:
        """Answer a micro-batch of queries; returns answers in order.

        Single-source queries on the same decomposable predicate coalesce
        into one batched dense fixpoint; same-(pred, adornment)-shape tuple
        queries coalesce into one qid-tagged tuple fixpoint (per-seed
        attribution splits the union back per query); everything else runs
        through the memoized tuple templates one by one.  Every answer lands
        in the result cache individually, so later singleton queries hit.

        Internally two phases: :meth:`launch_batch` dispatches the device
        fixpoints, :meth:`finalize_batch` does the host-side splitting,
        formatting and cache fill — the admission front-end
        (``admission.py``) runs them on different threads so batch *k*'s
        host work overlaps batch *k+1*'s device fixpoint.
        """
        with self.lock:
            return self.finalize_batch(self.launch_batch(queries))

    def launch_batch(self, queries: list) -> "_PendingBatch":
        """Phase 1 of :meth:`ask_batch`: classify queries (cache hit / EDB
        selection / dense-coalescible / tuple shape) and dispatch every
        device fixpoint.  Returns the in-flight state for
        :meth:`finalize_batch`; must run under :attr:`lock`."""
        seq, ahead = self._number_flush()
        with self.lock, self.tracer.span("launch_batch", cat="service",
                                         batch=len(queries), flush=seq,
                                         ahead=ahead):
            self._h_batch.observe(len(queries))
            qlits = [self._as_literal(s) for s in queries]
            pending = _PendingBatch(epoch=self.epoch, qlits=qlits,
                                    out=[None] * len(qlits), seq=seq)
            dense: dict[str, list[tuple[int, int, Literal]]] = {}
            singles: list[tuple[int, Literal]] = []
            for i, q in enumerate(qlits):
                key = self._cache_key(q)
                ent = self.cache.get(key)
                if ent is not None:
                    assert ent.epoch == self.epoch, \
                        "stale cache entry survived append"
                    pending.hits.append((i, ent))
                    continue
                if q.pred in self.db:  # EDB query: a pure selection
                    pending.out[i] = self._ask_edb(q)
                    continue
                src = self._dense_source(q)
                if src is not None:
                    dense.setdefault(q.pred, []).append((i, src, q))
                else:
                    singles.append((i, q))
            for pred, items in dense.items():
                pending.dense.append(self._launch_dense_batch(pred, items))
            # group tuple queries by (pred, adornment) shape; same-shape
            # groups of >= 2 distinct queries share one qid-tagged fixpoint.
            # Mixed shapes NEVER coalesce (no shared seed schema).
            shapes = _batch.coalesce_by_shape(
                singles, lambda q: (q.pred, self._adorn(q)))
            for (pred, adn), items in shapes.items():
                pending.tuples.append(
                    self._launch_tuple_group(pred, adn, items))
            if seq is not None:
                self._unsynced += 1  # until finalize_batch has synced it
            return pending

    def _number_flush(self) -> tuple:
        """``(seq, ahead)`` for a flush's spans: its number, and the flushes
        launched and not yet synced as it starts (the device queue it
        joins).  ``(None, None)`` with the tracer off: nothing is counted
        or locked for the trace."""
        if not self.tracer.enabled:
            return None, None
        with self.lock:
            self._flush_seq += 1
            return self._flush_seq, self._unsynced

    def finalize_batch(self, pending: "_PendingBatch") -> list:
        """Phase 2 of :meth:`ask_batch`: block on the launched device
        tables, split/format per query (host work, runs *outside* the
        service lock), then fill the result cache and the answer slots
        under the lock.  The epoch assert is the fencing invariant: an
        append must never land between a batch's launch and its cache fill
        (``incremental.EpochFence`` enforces this for the async front-end).
        """
        with self.tracer.span("finalize_batch", cat="service",
                              batch=len(pending.qlits), flush=pending.seq):
            t_fin = time.monotonic()
            try:
                dense_done = [self._sync_dense(pending.seq, *group)
                              for group in pending.dense]
            finally:
                if pending.seq is not None:
                    with self.lock:
                        self._unsynced -= 1
            tuple_done = []
            for pred, items, uniq, launched, results in pending.tuples:
                if results is None:  # batched: split the captured model now
                    tpl, run = launched
                    with self.tracer.span("tuple_split", cat="service",
                                          pred=pred):
                        answers = tpl.finalize_launched(self, run)
                    results = {key: _freeze(res)
                               for (key, _), res in zip(uniq, answers)}
                tuple_done.append((pred, items, results))
            with self.lock, self.tracer.span("cache_fill", cat="service"):
                assert pending.epoch == self.epoch, \
                    "append overtook an in-flight batch (epoch fence violated)"
                out = pending.out
                for i, ent in pending.hits:
                    out[i] = self._entry_result(ent)
                for pred, ds, items, uniq, formatted, counted in dense_done:
                    if counted is not None:
                        ds.tally(*counted)
                    final: dict[int, object] = {}
                    for s, (fmt, raw) in formatted.items():
                        self._cache_dense(pred, s, fmt, raw)
                        final[s] = fmt
                    for s in uniq:
                        if s not in final:  # beyond the domain: unreachable
                            final[s] = self._empty_dense(ds, s)
                    for i, src, _ in items:
                        out[i] = final[src]
                for pred, items, results in tuple_done:
                    for key, res in results.items():
                        self.cache.put(key, CacheEntry("tuple", pred, res,
                                                       self.epoch))
                    for i, q in items:
                        out[i] = results[self._cache_key(q)]
                self._h_finalize.observe(time.monotonic() - t_fin)
                return out

    # -- appends -------------------------------------------------------------

    def append(self, rel: str, rows) -> "DatalogService":
        """Monotone EDB append: add facts, keep serving.

        Cached dense closures and batched tuple-template snapshots are
        *resumed* from their pre-append state over the appended EDB
        (``incremental.py``) so hot entries stay warm; everything else (and,
        under ``resume_min_hits``, the cold tail) is invalidated.
        """
        with self.lock, self.tracer.span("append", cat="service", rel=rel):
            if rel not in self.db:
                raise ValueError(
                    f"{rel!r} is not an EDB relation of this service "
                    f"(known: {sorted(self.db)}); appends are EDB-only")
            rows = _inc.validate_append(rows, self.db[rel].shape[1], self.bits)
            if self._durable is not None:
                # write-ahead: the record is durable BEFORE any in-memory
                # state mutates, so a crash anywhere below replays it
                self._durable.log_append(rel, rows, self.epoch + 1)
            # EDB relations stay SETS under appends (Engine normalization
            # dedupes at build; re-appended duplicates must not double-count
            # additive aggregate bindings on the next tuple evaluation)
            self.db[rel] = np.unique(
                np.concatenate([self.db[rel], rows], axis=0), axis=0)
            self.epoch += 1
            self.stats.appends += 1
            self._base.invalidate(rel)
            for tpl in self._templates.values():
                tpl.on_append(self, rel)
            refreshed = self._resume_tuple_snapshots(rel)
            self.cache.drop_where(
                lambda k, e: e.kind == "tuple" and k not in refreshed)
            for k, e in self.cache.items():
                if e.kind == "dense" and self._lowering(e.pred).edb != rel:
                    e.epoch = self.epoch  # untouched base relation: valid
            for pred, ds in self._dense.items():
                if ds.low.edb == rel:
                    self._refresh_dense(pred, ds, rows)
            if self._durable is not None:
                self._durable.maybe_snapshot(self)
            return self

    def snapshot(self, wait: bool = False) -> int | None:
        """Hand a consistent snapshot of the hot serving state to the
        background checkpoint writer (requires ``durable_dir=``); returns
        the published generation's step.  ``wait=True`` blocks until the
        atomic rename lands — use it before a planned shutdown so the next
        start recovers warm with an empty WAL suffix."""
        if self._durable is None:
            raise RuntimeError("snapshot() requires DatalogService("
                               "durable_dir=...)")
        with self.lock:
            step = self._durable.snapshot(self)
        if wait:
            self._durable.wait()
        return step

    def close(self) -> None:
        """Flush and release durable resources (no-op without
        ``durable_dir=``); the service stays usable for in-memory serving."""
        if self._durable is not None:
            self._durable.close()

    def cap_batch(self, max_batch: int) -> None:
        """Make ``max_batch`` the widest batch pad.  Every launch that the
        service sizes itself is then at most that wide: admission flushes,
        append resumes and the width kernels are tuned at.  Wider pads are
        dropped; a ``max_batch`` wider than the widest pad raises
        ``ValueError``, since relations may already be tuned below it."""
        max_batch = int(max_batch)
        with self.lock:
            if not 1 <= max_batch <= self.batch_pads[-1]:
                raise ValueError(
                    f"max_batch {max_batch} is outside 1..{self.batch_pads[-1]}"
                    f", the service's widest batch pad (batch_pads="
                    f"{self.batch_pads})")
            self.batch_pads = tuple(
                p for p in self.batch_pads if p < max_batch) + (max_batch,)

    def compile_batch(self, pred: str, batch: int):
        """Compile, without running it, the closure fixpoint that a flush of
        ``batch`` single-source ``pred`` queries launches on a CSR relation:
        its current layout, its kernels and the padded batch width.  Returns
        the ``jax.stages.Compiled``; its ``memory_analysis()`` is what one
        such launch needs on the device, so a deployment can size the
        admission ``max_batch`` before it serves.  Single-device launches
        only (a dense relation's need is its n×n matrix)."""
        with self.lock:
            low = self._lowering(pred)
            if low is None:
                raise PlanError(f"{pred!r} has no batched frontier fixpoint")
            ds = self._dense_state(pred)
            if self.mesh is not None or not ds.is_csr:
                raise ValueError(
                    f"compile_batch sizes single-device CSR launches; {pred!r}"
                    f" is {'mesh-sharded' if self.mesh is not None else 'dense'}")
            bp = _batch.pad_batch_size(max(int(batch), 1), self.batch_pads)
            init = jax.ShapeDtypeStruct((bp, ds.n_alloc), ds.sr.dtype)
            lowered = _sparse.lower_fixpoint_csr(
                ds.csr, init, spmv=self._spmv(low.kind, ds.csr))
        return lowered.compile()

    def _resume_tuple_snapshots(self, rel: str) -> dict:
        """Resume batched tuple templates from their fixpoint snapshots and
        refresh the per-qid cache entries; returns {cache_key: entry} of the
        refreshed answers (everything else invalidates).  Honors the
        ``resume_min_hits`` policy: snapshots none of whose entries are hot
        are dropped, and only still-cached hot answers refresh."""
        refreshed: dict = {}
        for tpl in self._templates.values():
            for skey in list(tpl._snaps):  # LRU of the last K batches
                snap = tpl._snaps[skey]
                keys = [self._cache_key(q) for q in snap.qlits]
                cached = [(k, self.cache.peek(k)) for k in keys]
                if rel not in tpl.reads:
                    # the template's program never reads the appended
                    # relation: its answers are untouched — revalidate
                    for k, e in cached:
                        if e is not None:
                            e.epoch = self.epoch
                            refreshed[k] = e
                    continue
                hot, cold = _inc.partition_resumable(
                    [((i, k), e) for i, (k, e) in enumerate(cached)
                     if e is not None], self.resume_min_hits,
                    self.resume_max_bytes)
                self.stats.dropped_cold += len(cold)
                if not hot:
                    del tpl._snaps[skey]
                    continue
                try:
                    # cold positions are filtered out of the resumed fixpoint
                    # (and the next snapshot) entirely — never maintained
                    pairs = tpl.resume_batch(
                        self, skey, keep=[i for (i, _), _ in hot])
                except (PlanError, CapacityError, ValueError):
                    tpl._snaps.pop(skey, None)
                    continue
                for q, res in pairs:
                    key = self._cache_key(q)
                    ent = CacheEntry("tuple", tpl.pred, _freeze(res),
                                     self.epoch)
                    self.cache.replace(key, ent)
                    refreshed[key] = ent
                    self.stats.resumed_tuple_rows += 1
        return refreshed

    # -- introspection -------------------------------------------------------

    def explain(self) -> dict:
        """Introspection report — ONE documented schema across the stack.

        Canonical keys:

        ``epoch``      service append epoch (int)
        ``service``    :class:`ServiceStats` counters as a flat dict
        ``cache``      ``{entries, hits, misses, evictions}``
        ``templates``  memoized ``pred/adornment`` shapes (sorted list)
        ``relations``  per-predicate carrier reports: ``{n, n_alloc,
                       semiring, repr, fixpoint}`` plus ``flips``/
                       ``last_flip`` after representation flips and
                       ``nnz``/``density``/``e_alloc``/``padding`` (the
                       sliced-ELL per-slice allocation report) for CSR.
                       ``fixpoint`` holds running totals over the serving
                       flushes' fixpoints: ``launches``, ``rows`` (live),
                       ``pad_rows``, ``iterations``, ``row_iterations``
                       (pad × iterations), ``active_rows`` (Σ unconverged
                       rows entering each step) and ``delta_facts`` (Σ
                       entries changed); append resumes are not counted
        ``kernels``    a ``tuning`` entry per tuned predicate (chosen
                       :class:`~repro.kernels.autotune.KernelConfig`,
                       measured gain, achieved-vs-peak fractions) when
                       ``tune=`` is on; empty otherwise

        The async front-end nests its report under ``admission``
        (``{queue, window, counters}`` — see
        :meth:`~repro.service.admission.AsyncDatalogService.explain`).

        The pre-PR-7 flat aliases (``stats``, ``dense``) are GONE after
        their one-release deprecation window — read ``service`` /
        ``relations``.
        """
        rep = {
            "epoch": self.epoch,
            "service": dataclasses.asdict(self.stats),
            "cache": {"entries": len(self.cache), "hits": self.cache.hits,
                      "misses": self.cache.misses,
                      "evictions": self.cache.evictions},
            "templates": sorted(
                f"{p}/{a}" + ("+qid" if t.batchable else "")
                + (f"+snap{len(t._snaps)}" if t._snaps else "")
                for (p, a), t in self._templates.items()),
            "relations": {p: {"n": ds.n, "n_alloc": ds.n_alloc,
                              "semiring": ds.sr.name,
                              "repr": "csr" if ds.is_csr else "dense",
                              "fixpoint": dict(ds.fixpoint),
                              **({"flips": ds.flips,
                                  "last_flip": ds.last_flip}
                                 if ds.flips else {}),
                              **({"nnz": int(ds.csr.nnz)
                                  + int(ds.csr.tail_nnz),
                                  "density": ds.csr.density(),
                                  "e_alloc": ds.csr.e_alloc,
                                  "padding": ds.csr.padding_waste()}
                                 if ds.is_csr else {})}
                          for p, ds in self._dense.items()},
            "kernels": {},
        }
        tuning = {p: ds.tuning for p, ds in self._dense.items() if ds.tuning}
        if tuning:
            rep["kernels"]["tuning"] = tuning
        if self._durable is not None:
            rep["durability"] = self._durable.report()
        return rep

    def _absorb_stats(self, m) -> None:
        """Export-time absorption (``MetricsRegistry.register_collector``):
        the hot paths keep their cheap dataclass ``+=``s; every exporter
        sees them through the unified ``datalog_*`` schema."""
        with self.lock:
            st = dataclasses.asdict(self.stats)
            cache_hits, cache_misses = self.cache.hits, self.cache.misses
            cache_evicts, cache_len = self.cache.evictions, len(self.cache)
            epoch = self.epoch
        fx = m.counter("datalog_fixpoints_total",
                       "batched frontier/tuple fixpoints launched, by repr")
        fx.set(st["dense_fixpoints"] - st["csr_fixpoints"], {"repr": "dense"})
        fx.set(st["csr_fixpoints"], {"repr": "csr"})
        fx.set(st["tuple_fixpoints"], {"repr": "tuple"})
        bq = m.counter("datalog_batched_queries_total",
                       "queries answered by batched fixpoints, by engine")
        bq.set(st["batched_queries"], {"engine": "frontier"})
        bq.set(st["tuple_batched_queries"], {"engine": "tuple"})
        for name, field, help_ in (
            ("datalog_plans_built_total", "plans_built",
             "query templates constructed (magic rewrite + plan)"),
            ("datalog_plan_hits_total", "plan_hits",
             "queries served by a memoized template"),
            ("datalog_tuple_runs_total", "tuple_runs",
             "PSN template evaluations"),
            ("datalog_appends_total", "appends", "monotone EDB appends"),
            ("datalog_resumed_rows_total", "resumed_rows",
             "cached dense closures refreshed by append-resume"),
            ("datalog_resumed_tuple_rows_total", "resumed_tuple_rows",
             "tuple answers refreshed by snapshot resume"),
            ("datalog_dropped_cold_total", "dropped_cold",
             "cold cache entries dropped instead of resumed"),
        ):
            m.counter(name, help_).set(st[field])
        m.counter("datalog_cache_hits_total",
                  "result-cache hits").set(cache_hits)
        m.counter("datalog_cache_misses_total",
                  "result-cache misses").set(cache_misses)
        m.counter("datalog_cache_evictions_total",
                  "result-cache evictions").set(cache_evicts)
        m.gauge("datalog_cache_entries",
                "resident result-cache entries").set(cache_len)
        m.gauge("datalog_epoch", "service append epoch").set(epoch)
        m.counter("datalog_fixpoint_traces_total",
                  "fixpoint jit compilations, process-wide").set(
            fixpoint_trace_count())

    # -- internals -----------------------------------------------------------

    def _as_literal(self, spec) -> Literal:
        q = as_query_literal(spec)
        limit = (1 << self.bits) - 1
        for a in q.args:
            if isinstance(a, Const) and not (0 <= a.value <= limit):
                raise ValueError(
                    f"query constant {a.value} exceeds the {self.bits}-bit "
                    "packed domain")
        if q.pred in self.db:
            arity = self.db[q.pred].shape[1]
        elif q.pred in self.program.idb_predicates():
            arity = self.program.rules_for(q.pred)[0].head.arity
        else:
            raise PlanError(f"unknown predicate {q.pred!r}")
        if len(q.args) != arity:
            raise PlanError(
                f"query {q!r} has arity {len(q.args)} but {q.pred} has "
                f"arity {arity}")
        return q

    def _cache_key(self, q: Literal):
        # free positions key on their variable-repetition pattern, not just
        # "free": tc(X, Y) and tc(X, X) are different queries
        seen: dict[str, int] = {}
        return (q.pred,) + tuple(
            int(a.value) if isinstance(a, Const)
            else f"~{seen.setdefault(a.name, i)}"
            for i, a in enumerate(q.args))

    def _ask_edb(self, q: Literal) -> np.ndarray:
        # Engine.ask owns the EDB-selection semantics (constant + repeated-
        # variable filters); the base engine shares this service's db dict
        return self._base.ask(q)

    def _lowering(self, pred: str) -> FrontierLowering | None:
        if pred not in self._lowerings:
            self._lowerings[pred] = detect_frontier_lowering(self.program, pred)
        return self._lowerings[pred]

    def _dense_source(self, q: Literal) -> int | None:
        if self._lowering(q.pred) is None:
            return None
        # repeated-variable tails route to the tuple path (shared predicate
        # with Engine.ask_dense keeps the two routers agreeing)
        return frontier_query_source(q)

    def _dense_state(self, pred: str) -> _DenseRelation:
        if pred not in self._dense:
            self._dense[pred] = _DenseRelation(self, self._lowering(pred))
        return self._dense[pred]

    def _matmul(self, sr):
        if self._matmul_opt is None:
            return None
        if self._matmul_opt == "pallas":
            from ..kernels import ops as kops
            return kops.frontier_matmul(sr.name)
        return self._matmul_opt

    def _spmv(self, kind: str, csr=None):
        """Sparse segment-step override (the CSR twin of ``_matmul``): the
        ``matmul='pallas'`` option maps onto the segment-semiring SpMV
        kernels; arbitrary dense callables stay dense-only.  A CSR carrying
        a tile-skip plan (the autotuner chose ``use_kernel``) also routes to
        the kernels — the plan is dead weight on the jnp path."""
        if self._matmul_opt == "pallas" or (
                csr is not None and csr.plan_cfg is not None):
            from ..kernels import ops as kops
            return kops.csr_frontier_step(kind)
        return None

    def _tuned_config(self, ds: _DenseRelation, edges):
        """Resolve the kernel config for a CSR (re)build under ``tune=``:
        a pinned :class:`~repro.kernels.autotune.KernelConfig` applies
        as-is; ``True`` runs the measured search (cached per graph-shape
        signature, so tail-fold rebuilds of a stable shape class don't
        re-measure).  Returns None when tuning is off (default layout)."""
        if not self.tune:
            ds.tuning = None
            return None
        from ..kernels import autotune as _at
        if isinstance(self.tune, _at.KernelConfig):
            ds.tuning = {"config": self.tune.as_dict(), "pinned": True}
            return self.tune
        # tuned at the widest pad (``cap_batch``): a kernel whose VMEM that
        # width refuses is never chosen
        res = _at.autotune(edges, ds.n_alloc, ds.low.kind,
                           batch=self.batch_pads[-1])
        ds.tuning = {**res.as_dict(), "pinned": False}
        return res.config

    def _format(self, ds: _DenseRelation, src: int, row):
        if ds.low.kind == "bool":
            return _batch.format_bool_row(src, row, ds.n)
        if ds.low.kind == "plustimes":
            return _batch.format_plustimes_row(src, row, ds.n)
        if ds.low.kind == "maxplus":
            return _batch.format_maxplus_row(src, row, ds.n)
        return _batch.format_minplus_row(src, row, ds.n)

    def _entry_result(self, ent: CacheEntry):
        if ent.result is None:  # append-resumed entry: format on first serve
            ent.result = _freeze(self._format(self._dense_state(ent.pred),
                                              ent.src, ent.raw))
        return ent.result

    def _empty_dense(self, ds: _DenseRelation, src: int):
        return self._format(ds, src, jnp.full((0,), ds.sr.zero))

    def _launch_dense_batch(self, pred: str, items):
        """Dispatch ONE batched closure fixpoint for a dense group; the
        returned :class:`DenseResult` table is lazy — formatting (and the
        implied device sync) happens in :meth:`finalize_batch`."""
        ds = self._dense_state(pred)
        uniq: list[int] = []
        for _, src, _ in items:
            if src not in uniq:
                uniq.append(src)
        in_range = [s for s in uniq if s < ds.n_alloc]
        res = None
        meta = {"t_launch": time.monotonic(), "b": len(in_range)}
        if in_range:
            with self.tracer.span("fixpoint", cat="device", pred=pred,
                                  repr="csr" if ds.is_csr else "dense",
                                  b=len(in_range)):
                res = ds.run_batch(self, in_range)
            self.stats.dense_fixpoints += 1
            self.stats.csr_fixpoints += 1 if ds.is_csr else 0
            self.stats.batched_queries += len(in_range)
        return (pred, ds, items, uniq, in_range, res, meta)

    def _sync_dense(self, seq, pred, ds, items, uniq, in_range, res, meta):
        """Wait for one dense group's fixpoint and format its rows.  ONE
        host transfer per group, the table and the loop's counters together
        (the device sync of the whole batched fixpoint); per-row jax indexing
        would compile a tiny gather per (shape, row) pair on the serving hot
        path.  Returns the group with its formatted rows and its counters
        ``(rows, pad, iterations, active_rows, delta_facts)``, or None where
        nothing was launched."""
        table = counted = None
        with self.tracer.span("device_sync", cat="device", pred=pred,
                              flush=seq) as sync:
            if in_range:
                table, iters, active, delta = jax.device_get(
                    (res.table, res.iterations, res.active_rows,
                     res.delta_facts))
                counted = (len(in_range), table.shape[0], int(iters),
                           int(active), int(delta))
                if self.tracer.enabled:
                    sync.annotate(**dict(zip(
                        ("rows", "pad", "iterations", "active_rows",
                         "delta_facts"), counted)))
        if in_range:
            self._h_device.observe(time.monotonic() - meta["t_launch"])
        formatted = {s: (self._format(ds, s, table[j]), table[j])
                     for j, s in enumerate(in_range)}
        return pred, ds, items, uniq, formatted, counted

    def _cache_dense(self, pred: str, src: int, formatted, raw):
        low = self._lowering(pred)
        arity = edge_arity(low.kind)
        # the canonical single-source pattern key: distinct free tail vars
        key = (pred, src) + tuple(f"~{i}" for i in range(1, arity))
        self.cache.put(key, CacheEntry("dense", pred, _freeze(formatted),
                                       self.epoch, src=src, raw=raw))

    def _refresh_dense(self, pred: str, ds: _DenseRelation, new_rows: np.ndarray):
        grown = ds.append(self, new_rows)
        entries, cold = _inc.partition_resumable(
            [(k, e) for k, e in self.cache.items()
             if e.kind == "dense" and e.pred == pred], self.resume_min_hits,
            self.resume_max_bytes)
        if cold:  # eviction-aware resume: drop the cold tail, don't maintain it
            cold_keys = {k for k, _ in cold}
            self.stats.dropped_cold += self.cache.drop_where(
                lambda k, e: k in cold_keys)
        # resume in launches of at most the widest batch pad: the cache can
        # hold more closures than one fixpoint of that width fits on the
        # device, and wider pads were never compiled
        top = self.batch_pads[-1]
        for lo in range(0, len(entries), top):
            self._resume_dense(pred, ds, entries[lo:lo + top], grown)

    def _resume_dense(self, pred: str, ds: _DenseRelation, entries: list,
                      grown: bool):
        srcs = [e.src for _, e in entries]
        prev = jnp.stack([e.raw for _, e in entries])
        if grown:
            prev = _inc.pad_rows(prev, ds.n_alloc, ds.sr.zero)
        if ds.sr.idempotent:
            seed = ds.seed_rows(srcs, self.batch_pads)
            table = ds.run_batch(self, srcs,
                                 init=_inc.resume_init(ds.sr, prev, seed)).table
        elif not len(ds.last_delta):
            # additive, nothing genuinely new (exact-duplicate appends):
            # set semantics says every total is unchanged — revalidate only
            table = prev
        else:
            # additive ⊕ cannot re-enter from prev ⊕ seed (already-counted
            # paths would double-count): replay the increment instead — the
            # accumulate fixpoint from the first-new-arc seed counts exactly
            # the paths that use an appended arc, and prev ⊕ that closure is
            # the post-append total (``incremental.replay_init``)
            init0 = _inc.replay_init(ds.sr, prev, srcs, ds.last_delta,
                                     ds.n_alloc)
            t = ds.run_batch(self, srcs, init=init0).table
            table = prev + t[:len(srcs)]
        if ds.sr.idempotent or len(ds.last_delta):
            self.stats.dense_fixpoints += 1
            self.stats.csr_fixpoints += 1 if ds.is_csr else 0
        self.stats.resumed_rows += len(entries)
        for j, (key, e) in enumerate(entries):
            # result=None defers answer formatting to the entry's next hit —
            # an append refreshes validity, serving formats
            self.cache.replace(key, CacheEntry(
                "dense", pred, None, self.epoch, src=e.src, raw=table[j]))

    def _adorn(self, q: Literal) -> str:
        return query_adornment(
            q, agg_positions(self.program).get(q.pred, -1))

    def _template(self, pred: str, adn: str,
                  q: Literal) -> tuple[_QueryTemplate, bool]:
        """Memoized template for a shape; returns (template, freshly_built)."""
        key = (pred, adn)
        tpl = self._templates.get(key)
        if tpl is None:
            tpl = _QueryTemplate(self, q, adn)
            self._templates[key] = tpl
            self.stats.plans_built += 1
            return tpl, True
        return tpl, False

    def _launch_tuple_group(self, pred: str, adn: str, items):
        """One (pred, adornment) shape group: launch the qid-tagged batched
        fixpoint when the shape allows it, otherwise run the sequential
        templates to completion (their answers are already host arrays)."""
        uniq: list[tuple[object, Literal]] = []
        seen: set = set()  # a cache key pins its shape, so per-group dedup
        for _, q in items:
            key = self._cache_key(q)
            if key not in seen:
                seen.add(key)
                uniq.append((key, q))
        launched = None
        results = None
        if len(uniq) > 1 and BOUND in adn:
            launched = self._launch_tuple_batch(pred, adn, uniq)
        if launched is None:  # singleton / unbatchable: sequential path
            results = {}
            for key, q in uniq:
                results[key] = _freeze(self._ask_tuple(q))
        return (pred, items, uniq, launched, results)

    def _launch_tuple_batch(self, pred: str, adn: str, uniq: list):
        """B same-shape tuple queries as ONE qid-tagged fixpoint; returns
        (template, launched-state) for the finalize split, or None to fall
        back to sequential runs (shape not batchable, or the union of
        demands overflowed a table)."""
        tpl, fresh = self._template(pred, adn, uniq[0][1])
        if not tpl.batchable:
            return None
        try:
            run = tpl.launch_batch(self, [q for _, q in uniq])
        except (PlanError, CapacityError, ValueError):
            return None
        self.stats.plan_hits += len(uniq) - (1 if fresh else 0)
        self.stats.tuple_runs += 1
        self.stats.tuple_fixpoints += 1
        self.stats.tuple_batched_queries += len(uniq)
        return (tpl, run)

    def _ask_tuple(self, q: Literal):
        adn = self._adorn(q)
        tpl, fresh = self._template(q.pred, adn, q)
        if not fresh:
            self.stats.plan_hits += 1
        self.stats.tuple_runs += 1
        return tpl.run(self, q)
