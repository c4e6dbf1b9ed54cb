"""Micro-batching: coalesce B single-source queries into one dense fixpoint.

B concurrent queries ``?- tc(s_i, Y)`` on the same decomposable predicate
share one evaluation: their frontier rows stack into a (B, n) matrix and the
semi-naive fixpoint runs once, each iteration a single ⊕.⊗ contraction on
the MXU (``kernels.boolmm`` / ``kernels.minplus`` batched variants) with
per-row convergence masking — one matmul serves the whole batch.

Batch sizes quantize to the service's pad levels (1, 8, 32, 128, ...) with
⊕-zero frontier rows, so every batch shape hits an already-compiled fixpoint
(padded rows are all-zero and fall out of the row mask after one iteration).

With a device mesh, the batch lowers to the distributed decomposable plan
instead (``distributed.tc_frontier_decomposable``): frontier rows shard
across devices exactly like the recursive relation in the paper's Fig. 4, so
the per-iteration join stays shuffle-free.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core import sparse as _sparse
from ..core.semiring import Semiring
from ..core.seminaive import (DenseResult, additive_max_iters,
                              check_additive_converged,
                              fixpoint_dense_cached)


def pad_batch_size(b: int, pads: tuple[int, ...]) -> int:
    """Smallest pad level >= b; beyond the largest level, its next multiple."""
    for p in pads:
        if b <= p:
            return p
    top = pads[-1]
    return ((b + top - 1) // top) * top


def coalesce_by_shape(items: list, shape_of) -> dict:
    """Group (index, query) pairs by ``shape_of(query)``, preserving order.

    The tuple-path analog of the dense per-predicate grouping above: queries
    sharing a (pred, adornment) shape may share one qid-tagged fixpoint
    (their demands share a seed schema); mixed shapes must NOT coalesce.
    """
    groups: dict = {}
    for i, q in items:
        groups.setdefault(shape_of(q), []).append((i, q))
    return groups


def run_frontier_batch(
    sr: Semiring,
    matrix: jax.Array,
    srcs: list[int],
    pads: tuple[int, ...],
    matmul=None,
    mesh=None,
    max_iters: int | None = None,
    init: jax.Array | None = None,
) -> DenseResult:
    """One batched fixpoint answering ``len(srcs)`` single-source queries.

    ``init`` overrides the (B, n) frontier seed — an append-resume passes the
    previously closed rows ⊕ the post-append seed rows (``incremental.py``)
    so resume and cold batches share this dispatch (and its compilations).
    Returns a :class:`DenseResult` whose table's first ``len(srcs)`` rows are
    the closure rows of the requested sources (pad rows follow).
    """
    b = len(srcs)
    bp = pad_batch_size(b, pads)
    if init is None:
        # build the seed at the PADDED size: eager gather/where executables
        # are cache-keyed by operand shape, so gathering the raw B rows and
        # concatenating fill would pay a per-B mini-compile for every fresh
        # batch size — and the admission front-end's flush sizes are
        # arrival-dependent.  Duplicate-gather then ⊕-zero the pad rows.
        idx = np.concatenate([np.asarray(srcs, np.int64),
                              np.full(bp - b, srcs[0], np.int64)])
        init = matrix[jnp.asarray(idx)]
        if bp > b:
            keep = jnp.arange(bp) < jnp.int32(b)
            init = jnp.where(keep[:, None], init,
                             jnp.asarray(sr.zero, matrix.dtype))
    elif bp > b:  # caller-built seed (append-resume): B = cache occupancy
        fill = jnp.full((bp - b, matrix.shape[1]), sr.zero, matrix.dtype)
        init = jnp.concatenate([init, fill])
    if not sr.idempotent:
        # additive ⊕ (plus-times counting) has no masked vector form: the
        # accumulate fixpoint sums init·Aᵏ over path lengths, bounded by the
        # acyclicity iteration budget — hitting it raises
        # FixpointDivergenceError instead of serving a truncated count.
        # The sharded twin is vector-form only, so additive batches run the
        # plain cached fixpoint.
        if max_iters is None:
            max_iters = additive_max_iters(matrix.shape[-1])
        res = fixpoint_dense_cached(sr, matrix, init, form="accumulate",
                                    matmul=matmul, max_iters=max_iters)
        return check_additive_converged(res, max_iters,
                                        "additive dense batch")
    if mesh is not None:
        # the sharded loop counts no rows: active_rows/delta_facts stay -1
        closed, iters = _sharded(mesh, sr, matrix, init, matmul, max_iters)
        return DenseResult(closed, iters, jnp.int64(0))
    return fixpoint_dense_cached(sr, matrix, init, form="vector",
                                 matmul=matmul, max_iters=max_iters)


def _sharded(mesh, sr, matrix, init, matmul, max_iters):
    from ..core.distributed import tc_frontier_decomposable
    return tc_frontier_decomposable(mesh, matrix, init, sr=sr, matmul=matmul,
                                    max_iters=max_iters)


def run_frontier_batch_csr(
    csr: "_sparse.CSRMatrix",
    srcs: list[int],
    pads: tuple[int, ...],
    spmv=None,
    mesh=None,
    max_iters: int | None = None,
    init: jax.Array | None = None,
) -> DenseResult:
    """CSR twin of :func:`run_frontier_batch`: the same (B, n) batched
    frontier fixpoint with per-row convergence masking, but each iteration is
    an O(B·|E|) segment step over the packed arcs instead of an O(B·n²)
    dense ⊕.⊗ product — the serving hot path's sparse representation.

    Batch sizes quantize to the same pad levels (⊕-zero rows), ``init``
    overrides the seed for append-resume, and a mesh shards the batch rows
    Fig.-4 style (``distributed.csr_frontier_decomposable``) — dispatch,
    padding and caching behave identically to the dense path by design, so
    the session layer swaps representations per relation without touching
    its batching or resume logic.
    """
    b = len(srcs)
    bp = pad_batch_size(b, pads)
    sr = csr.semiring
    if init is None:
        # padded-size seed for shape-stable eager dispatch (see the dense
        # twin above): duplicate-gather to bp rows, ⊕-zero the pad rows
        idx = np.concatenate([np.asarray(srcs, np.int64),
                              np.full(bp - b, srcs[0], np.int64)])
        init = _sparse.rows_from_sources(csr, idx)
        if bp > b:
            keep = jnp.arange(bp) < jnp.int32(b)
            init = jnp.where(keep[:, None], init,
                             jnp.asarray(sr.zero, init.dtype))
    elif bp > b:
        fill = jnp.full((bp - b, init.shape[1]), sr.zero, init.dtype)
        init = jnp.concatenate([init, fill])
    if not sr.idempotent:
        # additive CSR: fixpoint_csr routes non-idempotent carriers to its
        # accumulate branch internally; guard the iteration budget here so a
        # cyclic graph raises instead of truncating (see the dense twin)
        if max_iters is None:
            max_iters = additive_max_iters(csr.n_alloc)
        res = _sparse.fixpoint_csr_cached(csr, init, spmv=spmv,
                                          max_iters=max_iters)
        return check_additive_converged(res, max_iters, "additive CSR batch")
    if mesh is not None:
        from ..core.distributed import csr_frontier_decomposable
        closed, iters = csr_frontier_decomposable(mesh, csr, init, spmv=spmv,
                                                  max_iters=max_iters)
        return DenseResult(closed, iters, jnp.int64(0))
    return _sparse.fixpoint_csr_cached(csr, init, spmv=spmv,
                                       max_iters=max_iters)


# -- answer formatting (dense carrier row -> Engine.ask-shaped numpy) --------


def format_bool_row(src: int, row, n: int) -> np.ndarray:
    """(n_alloc,) bool closure row -> (k, 2) int64 tc rows for source src."""
    dst = np.nonzero(np.asarray(row[:n]))[0]
    return np.stack([np.full(len(dst), src, np.int64), dst.astype(np.int64)],
                    axis=1) if len(dst) else np.zeros((0, 2), np.int64)


def format_minplus_row(src: int, row, n: int) -> tuple[np.ndarray, np.ndarray]:
    """(n_alloc,) float32 distance row -> ((k, 2) rows, (k,) int64 values)."""
    d = np.asarray(row[:n])
    dst = np.nonzero(np.isfinite(d))[0]
    if not len(dst):
        return np.zeros((0, 2), np.int64), np.zeros((0,), np.int64)
    rows = np.stack([np.full(len(dst), src, np.int64), dst.astype(np.int64)],
                    axis=1)
    return rows, d[dst].astype(np.int64)


def format_maxplus_row(src: int, row, n: int) -> tuple[np.ndarray, np.ndarray]:
    """(n_alloc,) float32 longest-path row -> ((k, 2) rows, (k,) int64).

    Same finite mask as the min-plus formatter — the max-plus ⊕-zero is
    -inf, equally non-finite — kept as its own entry point so the carrier
    table stays one-kind-one-formatter."""
    return format_minplus_row(src, row, n)


def format_plustimes_row(src: int, row, n: int
                         ) -> tuple[np.ndarray, np.ndarray]:
    """(n_alloc,) float32 count/sum row -> ((k, 2) rows, (k,) int64 values).

    The additive ⊕-zero is 0.0, so non-zero entries are the destinations
    with at least one path.  Values round to int64 — the engine's packed
    domain is integral, and f32 keeps integer totals exact to 2^24."""
    d = np.asarray(row[:n])
    dst = np.nonzero(d != 0.0)[0]
    if not len(dst):
        return np.zeros((0, 2), np.int64), np.zeros((0,), np.int64)
    rows = np.stack([np.full(len(dst), src, np.int64), dst.astype(np.int64)],
                    axis=1)
    return rows, np.rint(d[dst]).astype(np.int64)
