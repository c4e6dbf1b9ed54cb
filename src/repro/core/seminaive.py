"""Semi-naive fixpoint evaluators.

Two engines, one semantics (tested for equivalence):

1. **Dense semiring engine** (``fixpoint_dense``) — the TPU-native adaptation
   (DESIGN.md §3): each iteration is one ⊕.⊗ matrix product on the MXU, with
   semi-naive evaluation realized as delta-row masking (idempotent ⊕) or
   delta accumulation (additive ⊕).  The hot contraction can be swapped for a
   Pallas kernel (``repro.kernels``).

2. **Tuple PSN engine** (``psn_fixpoint``) — the faithful port of the paper's
   Algorithm 1 (delta/all, subtract, distinct) over the static-shape tables
   of ``relation.py``, driving compiled ``RulePipeline``s from the planner.
   Handles multiple mutually-recursive predicates (the "driver" pattern of
   §6.2) and aggregate tables (PreM-transferred programs).

Both run under ``jax.lax.while_loop`` and are restart-idempotent (monotone
state), matching the SetRDD fault-tolerance argument.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .relation import EMPTY, AggTable, FactTable, Schema, expand_join
from .semiring import BOOL, MIN_PLUS, PLUS_TIMES, Semiring

# ---------------------------------------------------------------------------
# Trace accounting (shared by every shape-keyed jitted fixpoint)
# ---------------------------------------------------------------------------

#: process-wide count of fixpoint (re-)traces — group runners, cached dense
#: fixpoints and CSR fixpoints all bump it, so serving tests can assert warm
#: batches of ANY representation skip compilation.  Exposed through
#: ``engine.fixpoint_trace_count()``.
_TRACE_COUNT = 0
# traces fire from the admission front-end's dispatcher/finalizer/submitter
# threads concurrently; a bare += on the global is a lost-update race, and
# ci.sh asserts warm-batch stability off exact counts
_TRACE_LOCK = threading.Lock()


def bump_trace_count() -> None:
    """Call at trace time (inside a jitted body): executes once per compile."""
    global _TRACE_COUNT
    with _TRACE_LOCK:
        _TRACE_COUNT += 1


def trace_count() -> int:
    return _TRACE_COUNT


#: generated-fact accumulator dtype.  ``jnp.int64`` is a silent int32 under
#: default config (no ``jax_enable_x64``), so spell out the dtype that will
#: actually exist, and check counters against its real bound (``GEN_MAX``).
GEN_DTYPE = jnp.asarray(0, jnp.int64).dtype
GEN_MAX = jnp.iinfo(GEN_DTYPE).max


# ---------------------------------------------------------------------------
# Dense semiring fixpoints
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DenseResult:
    """A frontier fixpoint's table and the counters its loop carries.

    ``active_rows`` and ``delta_facts`` are -1 where the loop did not count
    them (the mesh-sharded fixpoint); readers skip -1."""

    table: jax.Array  # fixpoint matrix / vector
    iterations: jax.Array  # () int32
    generated: jax.Array  # () GEN_DTYPE — facts produced before dedup
    #: () int32 — Σ over iterations of the rows still unconverged entering
    #: each step, pad rows included (entries, for an (n,) vector, which
    #: masks per entry); the accumulate form counts rows whose Δ is non-zero
    active_rows: jax.Array | int = -1
    #: () GEN_DTYPE — Σ over iterations of the table entries each step
    #: changed (semi-naive Δ); the accumulate form counts each new Δ's
    #: non-zero entries, which equals ``generated``
    delta_facts: jax.Array | int = -1


def _ne(sr: Semiring, a, b):
    if sr.dtype == jnp.bool_:
        return a != b
    # inf-aware compare for tropical semirings
    return ~((a == b) | (jnp.isinf(a) & jnp.isinf(b) & (jnp.sign(a) == jnp.sign(b))))


def _row_counts(x: jax.Array, zero) -> jax.Array:
    """Per-row int32 counts of the cells of ``x`` that are not ``zero``.

    A row of n < 2^31 cells cannot overflow int32, so the only 64-bit
    values the counters need are the B row counts (:func:`_sum_counts`):
    the chip emulates 64-bit arithmetic, and a (B, n) table of it inside
    the fixpoint loop would cost more than the step it counts."""
    return jnp.sum(x != jnp.asarray(zero, x.dtype), axis=-1, dtype=jnp.int32)


def _sum_counts(counts: jax.Array) -> jax.Array:
    """Sum of per-row counts as a ``GEN_DTYPE`` scalar."""
    return jnp.sum(counts.astype(GEN_DTYPE))


def masked_fixpoint(sr: Semiring, init: jax.Array, step: Callable,
                    max_iters: int) -> DenseResult:
    """The idempotent semi-naive loop of every masked form, dense or CSR:
    ``D <- D ⊕ step(D, mask)`` until no row changes.

    ``step(D, mask)`` applies the form's Δ masking itself.  The change test
    counts changed entries per row once: their sum is the Δ counter and
    ``counts > 0`` the next mask, so the counters take no extra pass over
    the table."""
    zero = jnp.asarray(sr.zero, init.dtype)

    def cond(s):
        D, mask, it, gen, act, delta = s
        return jnp.any(mask) & (it < max_iters)

    def body(s):
        D, mask, it, gen, act, delta = s
        upd = step(D, mask)
        with jax.named_scope("fixpoint_converge"):
            Dn = sr.add(D, upd)
            changed = _ne(sr, Dn, D)
            counts = (jnp.sum(changed, axis=-1, dtype=jnp.int32)
                      if D.ndim > 1 else changed.astype(jnp.int32))
            gen = gen + _sum_counts(_row_counts(upd, zero))
            act = act + jnp.sum(mask, dtype=jnp.int32)
            delta = delta + _sum_counts(counts)
        return Dn, counts > 0, it + 1, gen, act, delta

    mask0 = jnp.ones(init.shape[:-1] if init.ndim > 1 else init.shape, bool)
    D, _, it, gen, act, delta = jax.lax.while_loop(
        cond, body, (init, mask0, jnp.int32(0), jnp.zeros((), GEN_DTYPE),
                     jnp.int32(0), jnp.zeros((), GEN_DTYPE)))
    return DenseResult(D, it, gen, act, delta)


def accumulate_fixpoint(sr: Semiring, init: jax.Array, step: Callable,
                        max_iters: int) -> DenseResult:
    """The additive loop: ``C = Σ Δ``, ``Δ <- step(Δ)`` until Δ drains or
    ``max_iters`` (which the host checks: :func:`check_additive_converged`).
    The per-row counts of each new Δ give the generated and Δ counters and
    the next test for a live row."""
    zero = jnp.asarray(sr.zero, init.dtype)

    def cond(s):
        total, delta, live, it, gen, act = s
        return jnp.any(live > 0) & (it < max_iters)

    def body(s):
        total, delta, live, it, gen, act = s
        new = step(delta)
        counts = _row_counts(new, zero)
        act = act + jnp.sum(live > 0, dtype=jnp.int32)
        gen = gen + _sum_counts(counts)
        return total + new, new, counts, it + 1, gen, act

    total, _, _, it, gen, act = jax.lax.while_loop(
        cond, body, (init, init, _row_counts(init, zero), jnp.int32(0),
                     jnp.zeros((), GEN_DTYPE), jnp.int32(0)))
    return DenseResult(total, it, gen, act, gen)


def fixpoint_dense(
    sr: Semiring,
    arc: jax.Array,
    init: jax.Array,
    form: str = "linear",
    matmul: Callable | None = None,
    max_iters: int | None = None,
) -> DenseResult:
    """Dense fixpoint over a semiring.

    form:
      'linear'     D <- D ⊕ (Δmask·D) ⊗ arc          (tc r2 / dpath r2')
      'nonlinear'  D <- D ⊕ D ⊗ D                    (dpath r5; log-depth)
      'vector'     d <- d ⊕ arcᵀ-propagate(d)        (CC label propagation;
                                                      d is (n,) and arc (n,n);
                                                      a (B, n) init runs B
                                                      frontiers as one batched
                                                      fixpoint with per-row
                                                      convergence masking)
      'sandwich'   S <- S ⊕ arcᵀ ⊗ (S ⊗ arc)         (same-generation)
      'accumulate' C = Σ Δ;  Δ <- Δ ⊗ arc            (path counting, +,×)
    """
    mm = matmul or sr.matmul
    # domain size is the LAST dim: a batched (B, n) vector init must iterate
    # to the domain's depth, not the batch's
    n = init.shape[-1]
    if max_iters is None:
        max_iters = 4 * n + 8

    if form == "accumulate":
        if sr.idempotent:
            raise ValueError("accumulate form is for additive semirings")
        return accumulate_fixpoint(sr, init, lambda d: mm(d, arc), max_iters)

    def step(D, mask):
        if form == "linear":
            Dm = jnp.where(mask[:, None], D, jnp.asarray(sr.zero, D.dtype))
            return mm(Dm, arc)
        if form == "nonlinear":
            # semi-naive for nonlinear: Δ⊗D ⊕ D⊗Δ (symbolically rewritten r5)
            Dm = jnp.where(mask[:, None], D, jnp.asarray(sr.zero, D.dtype))
            return sr.add(mm(Dm, D), mm(D, Dm))
        if form == "vector":
            # batched (B, n) frontiers mask converged *rows*, not elements
            rmask = mask if D.ndim == 1 else mask[:, None]
            dm = jnp.where(rmask, D, jnp.asarray(sr.zero, D.dtype))
            return mm(dm[None, :], arc)[0] if D.ndim == 1 else mm(dm, arc)
        if form == "sandwich":
            Dm = jnp.where(mask[:, None], D, jnp.asarray(sr.zero, D.dtype))
            return mm(_transpose_arc(sr, arc), mm(Dm, arc))
        raise ValueError(form)

    return masked_fixpoint(sr, init, step, max_iters)


def _transpose_arc(sr: Semiring, arc: jax.Array) -> jax.Array:
    return arc.T


# additive-⊕ termination -------------------------------------------------------
# Idempotent carriers converge unconditionally; the additive (+,×) carrier
# only terminates when the program is acyclic (paper §2.1's count/sum
# termination discussion).  The jitted while_loop cannot raise, so additive
# fixpoints run under a tight iteration bound and the *host* checks it after.


class FixpointDivergenceError(RuntimeError):
    """An additive (non-idempotent ⊕) fixpoint hit its iteration bound —
    the underlying graph is cyclic, so count/sum-in-recursion diverges."""


def additive_max_iters(n: int) -> int:
    """Iteration bound for accumulate-form fixpoints: an acyclic n-vertex
    graph's longest path has < n arcs, so the delta drains within n steps;
    hitting n + 2 means a cycle keeps feeding it."""
    return int(n) + 2


def check_additive_converged(res: DenseResult, max_iters: int,
                             what: str = "additive fixpoint") -> DenseResult:
    if int(res.iterations) >= max_iters:
        raise FixpointDivergenceError(
            f"{what} hit its iteration bound ({max_iters}): the graph is "
            "cyclic, so the (+,×) carrier has no finite fixpoint — additive "
            "aggregates in recursion require an acyclic EDB")
    return res


# convenience graph front-ends ------------------------------------------------


def transitive_closure_dense(adj: jax.Array, matmul=None) -> DenseResult:
    """tc(X,Y) over the boolean semiring; adj is (n,n) bool."""
    return fixpoint_dense(BOOL, adj, adj, form="linear", matmul=matmul)


def shortest_paths_dense(w: jax.Array, matmul=None) -> DenseResult:
    """All-pairs spath (Examples 2/3). w: (n,n) float32 with +inf for no arc."""
    return fixpoint_dense(MIN_PLUS, w, w, form="linear", matmul=matmul)


def same_generation_dense(adj: jax.Array, matmul=None) -> DenseResult:
    """sg(X,Y) (Example 11): exit = AᵀA \\ id, recurse S <- Aᵀ S A.

    Only the exit rule carries X != Y (the paper's r1); the recursive rule may
    re-derive diagonal entries (possible when the graph has self-loops)."""
    a = adj.astype(jnp.float32)
    exit_ = (a.T @ a) > 0
    exit_ = exit_ & ~jnp.eye(adj.shape[0], dtype=bool)
    return fixpoint_dense(BOOL, adj, exit_, form="sandwich", matmul=matmul)


def connected_components_dense(adj: jax.Array) -> DenseResult:
    """connComp (Example 7 r7.3/r7.4): min-label propagation, undirected view."""
    n = adj.shape[0]
    sym = adj | adj.T
    prop = jnp.where(sym, 0.0, jnp.inf).astype(jnp.float32)  # weight-0 arcs
    labels = jnp.arange(n, dtype=jnp.float32)
    return fixpoint_dense(MIN_PLUS, prop, labels, form="vector")


# magic-restricted single-source fast paths ----------------------------------
# A query binding the pivot argument of a decomposable program reduces the
# matrix fixpoint to a *vector* fixpoint seeded with the query frontier row —
# the dense-engine counterpart of the magic-sets rewrite.


def reachable_from_dense(adj: jax.Array, src: int, matmul=None) -> DenseResult:
    """``?- tc(src, Y)``: one-frontier reachability, O(e) per iteration."""
    return fixpoint_dense(BOOL, adj, adj[src], form="vector", matmul=matmul)


def single_source_distances_dense(w: jax.Array, src: int, matmul=None) -> DenseResult:
    """``?- spath(src, Z, D)``: single-source min-plus distances."""
    return fixpoint_dense(MIN_PLUS, w, w[src], form="vector", matmul=matmul)


# batched / cached front-ends (the serving layer's hot path) ------------------
# A micro-batch of B single-source queries on the same decomposable predicate
# shares ONE fixpoint: the frontier is a (B, n) matrix, each iteration one
# ⊕.⊗ product, with per-row convergence masking.  ``fixpoint_dense_cached``
# additionally runs under a shape-keyed jit so repeated batches of the same
# padded shape skip re-tracing the while_loop.


@functools.partial(jax.jit, static_argnames=("sr", "form", "matmul", "max_iters"))
def _fixpoint_dense_jit(sr, arc, init, form, matmul, max_iters):
    bump_trace_count()  # trace-time only: warm batches must not move it
    return fixpoint_dense(sr, arc, init, form=form, matmul=matmul,
                          max_iters=max_iters)


def fixpoint_dense_cached(
    sr: Semiring,
    arc: jax.Array,
    init: jax.Array,
    form: str = "linear",
    matmul: Callable | None = None,
    max_iters: int | None = None,
) -> DenseResult:
    """:func:`fixpoint_dense` under a shape-keyed jit.

    ``sr``/``form``/``matmul`` are static (hashable; pass module-level
    callables for ``matmul`` so the cache keys stay stable); ``arc``/``init``
    are traced, so repeat calls with the same padded shapes reuse the
    compiled while_loop.  ``max_iters`` is resolved here (it closes over the
    domain size) to keep the static key deterministic per shape.
    """
    if max_iters is None:
        max_iters = 4 * init.shape[-1] + 8
    return _fixpoint_dense_jit(sr, arc, init, form, matmul, max_iters)


def reachable_batch_dense(adj: jax.Array, srcs, matmul=None,
                          max_iters: int | None = None) -> DenseResult:
    """``?- tc(s, Y)`` for a batch of sources: one (B, n) masked fixpoint."""
    init = adj[jnp.asarray(srcs)]
    return fixpoint_dense_cached(BOOL, adj, init, form="vector", matmul=matmul,
                                 max_iters=max_iters)


def distances_batch_dense(w: jax.Array, srcs, matmul=None,
                          max_iters: int | None = None) -> DenseResult:
    """``?- spath(s, Z, D)`` for a batch of sources (min-plus carrier)."""
    init = w[jnp.asarray(srcs)]
    return fixpoint_dense_cached(MIN_PLUS, w, init, form="vector",
                                 matmul=matmul, max_iters=max_iters)


def counts_batch_dense(w: jax.Array, srcs, matmul=None,
                       max_iters: int | None = None) -> DenseResult:
    """``?- cpath(s, Z, C)`` for a batch of sources: plus-times path counts
    via the accumulate form (total = Σ_k w[s]·wᵏ), guarded by the additive
    iteration bound — raises :class:`FixpointDivergenceError` on cycles."""
    init = w[jnp.asarray(srcs)]
    if max_iters is None:
        max_iters = additive_max_iters(w.shape[-1])
    res = fixpoint_dense_cached(PLUS_TIMES, w, init, form="accumulate",
                                matmul=matmul, max_iters=max_iters)
    return check_additive_converged(res, max_iters, "plus-times batch")


# ---------------------------------------------------------------------------
# Tuple PSN — Algorithm 1, faithfully
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EdbIndex:
    """A base relation indexed for equi-joins on a column subset.

    ``keys`` are the join columns packed+sorted; payload columns are gathered
    into the same order.  This is the engine's build-side hash table.
    Registered as a pytree so indexes flow into cached jitted fixpoints as
    *arguments* (never baked trace constants — see ``engine.GroupExecutor``).
    """

    keys: jax.Array  # (n,) int64 sorted
    count: jax.Array  # () int32
    cols: tuple[jax.Array, ...]  # full tuple columns, sorted by keys


def quantize_rows(n: int, minimum: int = 8) -> int:
    """Shape bucket for data-dependent row counts: next power of two.

    Materialized intermediate strata (magic sets above all) have
    query-dependent cardinalities; padding their indexes/scans to bucketed
    capacities keeps the number of distinct jit shapes logarithmic, so warm
    queries hit already-compiled fixpoints (see ``engine.GroupExecutor``).
    """
    if n <= minimum:
        return minimum
    return 1 << (n - 1).bit_length()


def quantize_ladder(floor: int, stride: int, top: int) -> tuple[int, ...]:
    """Geometric capacity ladder for degree-class slices: power-of-two caps
    ``floor, floor<<stride, ...`` until the last rung covers ``top``.
    ``stride == 0`` degenerates to one rung at ``quantize_rows(top)`` — the
    single-width (legacy ELL) layout."""
    base = quantize_rows(max(int(floor), 1), minimum=1)
    if stride <= 0:
        return (quantize_rows(max(int(top), 1), minimum=base),)
    caps = [base]
    while caps[-1] < top:
        caps.append(caps[-1] << stride)
    return tuple(caps)


def pack_warm_rows(rows: np.ndarray, vals: np.ndarray | None, schema: Schema,
                   agg_init: int | None = None):
    """Pack previously-materialized rows for *warm-starting* a later fixpoint.

    Monotone tables make any earlier model a valid lower bound of the
    post-append model (the SetRDD restart argument), so an appended engine can
    re-enter the fixpoint from ``prev ∪ exit(T_new)`` instead of from scratch
    — convergence then costs the *delta's* propagation depth.  Rows pack to
    sorted int64 keys, EMPTY-padded to a :func:`quantize_rows` bucket so the
    warm arrays hit already-compiled fixpoint shapes as the model grows.
    """
    n = len(rows)
    cap = quantize_rows(max(n, 1))
    keys = np.full((cap,), np.iinfo(np.int64).max, np.int64)
    if n:
        rows = np.asarray(rows, np.int64)
        for c, hi in enumerate(schema.max_values()):
            col = rows[:, c]
            if col.min() < 0 or col.max() > hi:
                raise ValueError(
                    f"warm rows exceed the packed domain in column {c} "
                    f"(max {hi}); packing would silently truncate")
        packed = np.zeros((n,), np.int64)
        for c, shift in enumerate(schema.shifts):
            packed |= rows[:, c] << shift
        keys[:n] = packed
    if vals is None:
        return jnp.asarray(keys), None
    v = np.full((cap,), agg_init, np.int32)
    if n:
        v[:n] = np.asarray(vals, np.int32)
    return jnp.asarray(keys), jnp.asarray(v)


def build_edb_index(rows: np.ndarray, key_cols: tuple[int, ...], schema_bits: int,
                    minimum: int = 8) -> EdbIndex:
    """``minimum`` is the relation's shape-bucket floor (see
    :func:`quantize_rows`): relations whose cardinality hovers around a
    bucket boundary can pin a floor (``PlanOptions.bucket_floors``) so warm
    queries never straddle two compiled shapes."""
    rows = np.asarray(rows, np.int64)
    minimum = max(minimum, 8)
    if rows.ndim == 1:  # single-column relation (reshape(-1) chokes on 0 rows)
        rows = rows[:, None]
    if len(rows) == 0:
        # sentinel rows keep every downstream gather in-bounds; count=0
        # means no probe can match them (magic-restricted strata are often
        # empty)
        cap = quantize_rows(1, minimum=minimum)
        pad = np.zeros((cap, rows.shape[1] if rows.size or rows.ndim > 1 else 1), np.int64)
        return EdbIndex(
            keys=jnp.full((cap,), np.iinfo(np.int64).max, jnp.int64),
            count=jnp.asarray(0, jnp.int32),
            cols=tuple(jnp.asarray(pad[:, i], jnp.int32) for i in range(pad.shape[1])),
        )
    key_schema = Schema(tuple([schema_bits] * len(key_cols)))
    keys = np.zeros((len(rows),), np.int64)
    for c, shift in zip(key_cols, key_schema.shifts):
        keys = keys | (rows[:, c] << shift)
    order = np.argsort(keys, kind="stable")
    skeys = keys[order]
    scols = rows[order]
    cap = quantize_rows(len(rows), minimum=minimum)
    if cap > len(rows):
        # EMPTY-pad to the shape bucket: sentinels sort last and sit beyond
        # `count`, so no probe can match them
        pad = cap - len(rows)
        skeys = np.concatenate([skeys, np.full((pad,), np.iinfo(np.int64).max)])
        scols = np.concatenate([scols, np.zeros((pad, rows.shape[1]), np.int64)])
    return EdbIndex(
        keys=jnp.asarray(skeys),
        count=jnp.asarray(len(rows), jnp.int32),
        cols=tuple(jnp.asarray(scols[:, i], jnp.int32) for i in range(rows.shape[1])),
    )


@dataclasses.dataclass
class Bindings:
    """Variable bindings flowing through a rule body (columnar)."""

    cols: dict[str, jax.Array]  # var name -> (k,) int32/float32
    valid: jax.Array  # (k,) bool
    overflow: jax.Array  # () bool


def join_edb(b: Bindings, index: EdbIndex, probe_vars, build_key_cols, intro, schema_bits, out_cap) -> Bindings:
    """Join the binding table against an EDB index; introduce new columns.

    ``probe_vars`` entries are binding-column names or int constants — the
    planner pushes query/rule constants down to constant probes here instead
    of post-filtering the joined result.
    """
    key_schema = Schema(tuple([schema_bits] * len(probe_vars)))
    shape = b.valid.shape
    pcols = [b.cols[v] if isinstance(v, str) else jnp.full(shape, v, jnp.int32)
             for v in probe_vars]
    probe = key_schema.pack(pcols)
    probe = jnp.where(b.valid, probe, EMPTY)
    pi, bi, valid, ovf = expand_join(probe, b.valid, index.keys, index.count, out_cap)
    cols = {v: c[pi] for v, c in b.cols.items()}
    for var, col_idx in intro.items():
        cols[var] = index.cols[col_idx][bi]
    return Bindings(cols, valid, b.overflow | ovf)


def join_idb_prefix(b: Bindings, table_keys, table_count, probe_vars, pred_schema: Schema,
                    n_key_cols: int, values, intro_vars, out_cap) -> Bindings:
    """Join bindings against an IDB table on a *prefix* of its columns.

    IDB tables are sorted by their full packed tuple, hence sorted by any
    column prefix; a range query over the high bits finds all matches without
    re-indexing the (per-iteration-changing) table.
    """
    prefix_bits = sum(pred_schema.bits[:n_key_cols])
    rem_shift = sum(pred_schema.bits[n_key_cols:])
    key_schema = Schema(tuple(pred_schema.bits[:n_key_cols]))
    probe_prefix = key_schema.pack([b.cols[v] for v in probe_vars])
    lo_key = probe_prefix << rem_shift
    hi_key = jnp.where(b.valid, (probe_prefix + 1) << rem_shift, EMPTY)
    lo = jnp.searchsorted(table_keys, jnp.where(b.valid, lo_key, EMPTY))
    hi = jnp.searchsorted(table_keys, hi_key)
    hi = jnp.minimum(hi, table_count)
    matches = jnp.where(b.valid, jnp.maximum(hi - lo, 0), 0)
    offsets = jnp.cumsum(matches)
    total = offsets[-1]
    starts = offsets - matches
    slot = jnp.arange(out_cap)
    pidx = jnp.clip(jnp.searchsorted(offsets, slot, side="right"), 0, probe_prefix.shape[0] - 1)
    rank = slot - starts[pidx]
    tidx = jnp.clip(lo[pidx] + rank, 0, table_keys.shape[0] - 1)
    valid = slot < jnp.minimum(total, out_cap)
    cols = {v: c[pidx] for v, c in b.cols.items()}
    unpacked = pred_schema.unpack(table_keys[tidx])
    for var, col_idx in intro_vars.items():
        if col_idx == "value":
            cols[var] = values[tidx]
        else:
            cols[var] = unpacked[col_idx]
    return Bindings(cols, valid, b.overflow | (total > out_cap))
