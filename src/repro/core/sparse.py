"""CSR-packed sparse frontier engine — O(|E|)-per-iteration fixpoints.

The dense serving path (``seminaive.fixpoint_dense`` / ``service.batch``)
multiplies an ``n_align``-rounded O(n²) adjacency every iteration.  On the
common BigDatalog workload — large sparse graphs with |E| ≪ n² — almost all
of that FLOP and HBM traffic is ⊕-zero padding, exactly the memory-layout
bottleneck Fan et al. identify as dominant for recursive queries.  This
module packs the base relation once into CSR and runs the same semi-naive
frontier fixpoint over the *edges*:

    out[b, dst] ⊕= frontier[b, src] ⊗ val        for every packed arc

one gather + segment-⊕ scatter per iteration instead of a dense ⊕.⊗ product
— O(B·|E|) work, O(|E|) memory traffic.

Layout (:class:`CSRMatrix`):

* ``row_ptr``/``col_idx``/``edge_val`` — the canonical CSR spine (arcs
  sorted by source, ``row_ptr[v]:row_ptr[v+1]`` spans v's out-edges), plus
  ``src_idx`` — the expanded row ids (CSR-packed COO) that make the edge
  gather one vectorized operation instead of a per-row loop;
* ``ell_slices``/``ell_rank`` — the **sliced-ELL** segment index: vertices
  partition into degree classes (capacity ladder ``floor·(2^stride)^i``,
  see :func:`~repro.core.seminaive.quantize_ladder`) and each slice packs
  its vertices' in-edge positions at *its own* capacity.  XLA lane scatter
  serializes per index, so the segment-⊕ instead runs scatter-free: one
  gather + (B, rows_s, cap_s) ⊕-reduce per slice, concatenated and
  lane-gathered back to vertex order through ``ell_rank``.  A single-width
  ELL pads every vertex to the max in-degree — one power-law hub inflates
  ``e_alloc`` for the whole spine; slicing bounds padding per degree class
  (a vertex in a stride-1 slice has indeg > cap/2, so spine allocation stays
  ≤ ~2·|E| regardless of the tail).  ``ell_cfg=(floor, 0)`` degenerates to
  the legacy single-width layout;
* ``nnz`` padded to a :func:`~repro.core.seminaive.quantize_rows` bucket
  with ⊕-zero sentinel arcs (slice pads point at a sentinel slot) —
  warm graphs whose edge counts and degree profiles stay inside their
  buckets reuse compiled fixpoints, the serving layer's shape-stability
  contract;
* an optional **tile-skip plan** (``plan_tile``/``plan_chunk``/
  ``plan_first`` + static ``plan_cfg``): the host-precomputed worklist of
  (column-tile, edge-chunk) pairs with at least one destination hit, ridden
  into the Pallas min-plus kernel as scalar-prefetch operands so its grid
  visits O(hits) blocks instead of the dense O(cap·n/(chunk·bn)) cross
  product (``kernels.spmv.csr_minplus_spmv_tiled``);
* a COO **tail** for monotone appends: new arcs land in a bucketed tail
  (with its own small single-width ELL index — one extra segment pass per
  iteration) and fold into the CSR spine only when the tail outgrows
  ``rebuild_frac`` of the packed arcs — appends stay O(|ΔE|) instead of
  re-sorting the world.  Rebuilds carry ``ell_cfg``/``plan_cfg`` forward.

``fixpoint_csr`` / ``fixpoint_csr_cached`` mirror ``fixpoint_dense`` /
``fixpoint_dense_cached`` (same :class:`~repro.core.seminaive.DenseResult`,
same per-row convergence masking, same shape-keyed jit) so the serving stack
swaps representations behind one batching interface.  The Pallas kernels in
``repro.kernels.spmv`` implement the same segment-semiring contraction with
explicit tiling; the jnp gather/reduce here is the oracle and CPU path.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .semiring import Semiring, carrier_for
from .seminaive import (DenseResult, accumulate_fixpoint, additive_max_iters,
                        bump_trace_count, check_additive_converged,
                        masked_fixpoint, quantize_ladder, quantize_rows)

#: density |E|/n² below which the serving layer prefers CSR over the dense
#: matrix (the auto heuristic; PlanOptions.sparse / DatalogService(sparse=)
#: force either).  Above it the dense ⊕.⊗ product's regular layout wins.
DEFAULT_SPARSE_THRESHOLD = 1 / 64

#: default sliced-ELL capacity ladder: floor 1, stride 1 — pure power-of-two
#: degree classes (caps 1, 2, 4, ...).  ``(f, 0)`` is single-width legacy.
DEFAULT_ELL_CFG = (1, 1)


def prefer_csr(nnz: int, n: int, threshold: float = DEFAULT_SPARSE_THRESHOLD) -> bool:
    """The density heuristic: CSR pays off when |E|/n² is small."""
    if n <= 0:
        return False
    return (nnz / float(n * n)) < threshold


def _semiring_of(kind: str) -> Semiring:
    # routed through the carrier table — an unknown kind is a typed error,
    # never a silent min-plus fallback (the session.py misrouting bug class)
    return carrier_for(kind)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("row_ptr", "col_idx", "edge_val", "src_idx", "ell_slices",
                 "ell_rank", "nnz", "tail_src", "tail_dst", "tail_val",
                 "tail_ell", "tail_nnz", "plan_tile", "plan_chunk",
                 "plan_first"),
    meta_fields=("n", "n_alloc", "kind", "ell_cfg", "plan_cfg"),
)
@dataclasses.dataclass(frozen=True)
class CSRMatrix:
    """A base relation packed for sparse frontier fixpoints.

    Registered as a pytree (shape-keyed jit argument, like ``EdbIndex``):
    the *data* fields trace, the domain/bucket sizes are static metadata, so
    two graphs sharing buckets share one compiled fixpoint.
    """

    row_ptr: jax.Array  # (n_alloc + 1,) int32 — CSR spine over sources
    col_idx: jax.Array  # (cap,) int32 — destinations, source-sorted
    edge_val: jax.Array  # (cap,) carrier — True / weight; ⊕-zero sentinels
    src_idx: jax.Array  # (cap,) int32 — expanded row ids (packed COO)
    ell_slices: tuple  # per-degree-class (rows_s, cap_s) int32 tables of
    #                    packed in-edge positions (sentinel-slot padded):
    #                    the scatter-free sliced segment map
    ell_rank: jax.Array  # (n_alloc,) int32 — vertex -> its row in the
    #                      slice-concatenated reduce output (dead vertices
    #                      share the all-sentinel row 0)
    nnz: jax.Array  # () int32 — live arcs in the CSR spine
    tail_src: jax.Array  # (tail_cap,) int32 — appended arcs (COO tail)
    tail_dst: jax.Array  # (tail_cap,) int32
    tail_val: jax.Array  # (tail_cap,) carrier
    tail_ell: jax.Array  # (n_alloc, tail_deg_cap) int32 — tail segment map
    tail_nnz: jax.Array  # () int32
    plan_tile: jax.Array | None  # (W,) int32 tile-skip worklist (see module
    plan_chunk: jax.Array | None  # doc); None when no kernel plan was built
    plan_first: jax.Array | None  # (W,) int32 — 1 at a tile's first visit
    n: int  # live domain size AT BUILD TIME — static metadata (part of the
    #         jit cache key), so tail appends never touch it: the serving
    #         layer tracks live growth itself and the segment maps cover all
    #         of n_alloc regardless
    n_alloc: int  # padded domain (dense twin's n_align contract)
    kind: str  # 'bool' | 'minplus' | 'maxplus' | 'plustimes'
    ell_cfg: tuple  # (floor, stride) capacity-ladder config; stride 0 =
    #                 single-width (legacy) ELL
    plan_cfg: tuple | None  # (chunk, bn) of the tile-skip plan, or None

    @property
    def semiring(self) -> Semiring:
        return _semiring_of(self.kind)

    @property
    def capacity(self) -> int:
        return int(self.col_idx.shape[0])

    @property
    def tail_capacity(self) -> int:
        return int(self.tail_src.shape[0])

    @property
    def deg_cap(self) -> int:
        """Widest slice capacity (the single-width ELL width when stride=0)."""
        return max(int(t.shape[1]) for t in self.ell_slices)

    @property
    def e_alloc(self) -> int:
        """Allocated segment-reduce slots (sliced spine + tail): the ELL
        padding overhead the autotuner's cost model charges per iteration."""
        spine = sum(int(t.shape[0]) * int(t.shape[1]) for t in self.ell_slices)
        return spine + int(np.prod(self.tail_ell.shape))

    def density(self) -> float:
        if self.n <= 0:
            return 0.0
        return float(int(self.nnz) + int(self.tail_nnz)) / float(self.n * self.n)

    def padding_waste(self) -> dict:
        """Per-slice allocation report: how much of the sliced spine is pad.

        ``waste`` is ``e_alloc_spine / max(nnz, 1)`` — the sliced-ELL win
        over single-width shows up here (``benchmarks/bench_buckets.py``
        records it; the serving layer surfaces it through ``explain()``).
        """
        sent = self.capacity - 1
        slices = []
        for t in self.ell_slices:
            tn = np.asarray(t)
            live = int((tn != sent).sum())
            slices.append({"rows": int(t.shape[0]), "cap": int(t.shape[1]),
                           "alloc": int(tn.size), "live": live})
        alloc = sum(s["alloc"] for s in slices)
        nnz = int(self.nnz)
        return {"slices": slices, "e_alloc": alloc, "nnz": nnz,
                "waste": alloc / max(nnz, 1)}

    def edges_numpy(self) -> np.ndarray:
        """The live arcs back as an (m, 2|3) int64 edge list (spine + tail)."""
        m, t = int(self.nnz), int(self.tail_nnz)
        src = np.concatenate([np.asarray(self.src_idx[:m]),
                              np.asarray(self.tail_src[:t])])
        dst = np.concatenate([np.asarray(self.col_idx[:m]),
                              np.asarray(self.tail_dst[:t])])
        if self.kind == "bool":
            return np.stack([src, dst], axis=1).astype(np.int64)
        val = np.concatenate([np.asarray(self.edge_val[:m]),
                              np.asarray(self.tail_val[:t])])
        return np.stack([src.astype(np.int64), dst.astype(np.int64),
                         val.astype(np.int64)], axis=1)


def _pack_edges(edges: np.ndarray, kind: str):
    """Normalize an (m, 2|3) edge array into src/dst/val numpy columns."""
    edges = np.asarray(edges, np.int64)
    if edges.ndim != 2 or edges.shape[1] not in (2, 3):
        raise ValueError(f"edge list must be (m, 2|3), got {edges.shape}")
    if len(edges) and not _semiring_of(kind).idempotent:
        # set semantics: exact duplicate facts collapse BEFORE the segment
        # sum — an idempotent ⊕ absorbs duplicates for free, the additive
        # (+,×) carrier would double-bill them.  Parallel arcs with distinct
        # weights are distinct facts and still sum, as they should.
        edges = np.unique(edges, axis=0)
    src = edges[:, 0].astype(np.int32)
    dst = edges[:, 1].astype(np.int32)
    if kind == "bool":
        val = np.ones(len(edges), bool)
    else:
        if edges.shape[1] != 3:
            raise ValueError(f"{kind} CSR wants (src, dst, weight) rows")
        val = edges[:, 2].astype(np.float32)
    return src, dst, val


def _ell_index(dst: np.ndarray, m: int, n_alloc: int,
               sentinel_pos: int) -> np.ndarray:
    """Single-width segment map (the COO tail's layout): for every vertex,
    the packed positions of its in-edges, right-padded with ``sentinel_pos``
    (a slot whose value is the ⊕-zero) to the bucketed max in-degree."""
    live = dst[:m]
    indeg = np.bincount(live, minlength=n_alloc) if m else \
        np.zeros(n_alloc, np.int64)
    k = quantize_rows(int(indeg.max()) if m else 1, minimum=1)
    ell = np.full((n_alloc, k), sentinel_pos, np.int32)
    if m:
        order = np.argsort(live, kind="stable")  # positions grouped by dst
        sorted_dst = live[order]
        starts = np.cumsum(indeg) - indeg
        rank = np.arange(m) - starts[sorted_dst]
        ell[sorted_dst, rank] = order
    return ell


def _sliced_ell_index(dst: np.ndarray, m: int, n_alloc: int,
                      sentinel_pos: int, ell_cfg: tuple):
    """The sliced-ELL segment map: ``(slices, rank)``.

    Vertices with in-degree in ``(caps[s-1], caps[s]]`` land in slice ``s``
    (ladder from :func:`quantize_ladder`); each slice is a
    ``(rows_s, caps[s])`` table of packed in-edge positions, sentinel-padded.
    Row counts are EXACT and empty rungs are dropped — rounding rows up (or
    keeping an all-pad hub slice at 8 rows) voids the per-slice padding
    bound that is the whole point; the price is a retrace when a rebuild
    shifts the degree profile, which a rebuild pays anyway when its edge
    bucket moves.  The first kept slice's row 0 is a shared all-sentinel
    row: every zero-in-degree vertex's ``rank`` points there, so dead
    vertices cost one row total instead of one row each (the single-width
    layout's other hidden pad).
    """
    floor, stride = ell_cfg
    live = dst[:m]
    indeg = np.bincount(live, minlength=n_alloc) if m else \
        np.zeros(n_alloc, np.int64)
    max_d = int(indeg.max()) if m else 0
    caps = np.asarray(quantize_ladder(floor, stride, max_d), np.int64)
    live_v = np.nonzero(indeg > 0)[0]
    # first ladder rung covering each live vertex's in-degree
    slice_of = np.searchsorted(caps, indeg[live_v], side="left")
    rank = np.zeros(n_alloc, np.int32)  # dead vertices -> shared row 0
    tables = []
    if m:
        order = np.argsort(live, kind="stable")
        sorted_dst = live[order]
        starts = np.cumsum(indeg) - indeg
        edge_rank = np.arange(m) - starts[sorted_dst]
        edge_slice = np.searchsorted(caps, indeg[sorted_dst], side="left")
    row_of = np.zeros(n_alloc, np.int64)
    off = 0
    for s, cap in enumerate(caps):
        vs = live_v[slice_of == s]
        base = 1 if not tables else 0  # the shared sentinel row
        if not len(vs) and not base:
            continue  # empty rung: no table at all
        rows = len(vs) + base
        tbl = np.full((rows, int(cap)), sentinel_pos, np.int32)
        row_of[vs] = base + np.arange(len(vs))
        rank[vs] = off + base + np.arange(len(vs))
        if m:
            me = edge_slice == s
            tbl[row_of[sorted_dst[me]], edge_rank[me]] = order[me]
        tables.append(tbl)
        off += rows
    return tuple(tables), rank


def _tile_plan(dst: np.ndarray, m: int, cap: int, n_alloc: int,
               chunk: int, bn: int):
    """Host-side tile-skip worklist for the Pallas min-plus kernel: the
    (column-tile, edge-chunk) pairs where at least one live arc's destination
    lands in the tile, sorted by tile (output blocks must be revisited
    contiguously), each tile's first visit flagged for the ⊕-identity init.

    Empty tiles keep one dummy (tile, chunk 0) item so the init still fires
    (a chunk with no hits contributes only masked-out +inf).  The list pads
    to a :func:`quantize_rows` bucket by repeating the last item — safe
    because ⊕ is idempotent — so warm graphs reuse compiled grids.
    """
    from ..kernels.spmv import padded_width
    nt, nchunks = padded_width(n_alloc, bn) // bn, -(-cap // chunk)
    hits = np.zeros((nt, nchunks), bool)
    if m:
        hits[dst[:m] // bn, np.arange(m) // chunk] = True
    tiles, chunks, first = [], [], []
    for t in range(nt):
        cs = np.nonzero(hits[t])[0]
        if len(cs) == 0:
            cs = np.zeros(1, np.int64)
        tiles.extend([t] * len(cs))
        chunks.extend(cs.tolist())
        first.extend([1] + [0] * (len(cs) - 1))
    pad = quantize_rows(len(tiles), minimum=8) - len(tiles)
    tiles += [tiles[-1]] * pad
    chunks += [chunks[-1]] * pad
    first += [0] * pad
    return (np.asarray(tiles, np.int32), np.asarray(chunks, np.int32),
            np.asarray(first, np.int32))


def build_csr(edges: np.ndarray, n_alloc: int, kind: str = "bool",
              tail_min: int = 8, ell_cfg: tuple = DEFAULT_ELL_CFG,
              kernel_plan: tuple | None = None) -> CSRMatrix:
    """Pack an edge list into a :class:`CSRMatrix` over ``n_alloc`` vertices.

    Arcs sort by (src, dst); ``nnz`` pads to a power-of-two bucket (always
    leaving at least one slot free) with sentinel arcs whose ``edge_val`` is
    the ⊕-zero (False / +inf / -inf / 0) so they can never contribute — the
    sparse twin of ``build_edb_index``'s EMPTY pad.  Slice pad entries point
    at the last sentinel slot.  Duplicate arcs under an idempotent ⊕ need no
    dedup; the additive plus-times carrier dedupes exact duplicate rows in
    ``_pack_edges`` (set semantics) before the segment sum.

    ``ell_cfg=(floor, stride)`` sets the sliced-ELL capacity ladder
    (``stride=0`` = single-width legacy); ``kernel_plan=(chunk, bn)`` also
    precomputes the Pallas tile-skip worklist for those block sizes (the
    autotuner's knobs — see ``kernels.autotune``), rounded to the blocks the
    kernel can tile (``kernels.spmv.edge_chunk`` / ``column_tile``).
    """
    src, dst, val = _pack_edges(edges, kind)
    m = len(src)
    n = int(max(src.max(), dst.max())) + 1 if m else 0
    if n > n_alloc:
        raise ValueError(f"edges reference vertex {n - 1} >= n_alloc {n_alloc}")
    order = np.lexsort((dst, src))
    src, dst, val = src[order], dst[order], val[order]
    counts = np.bincount(src, minlength=n_alloc) if m else np.zeros(n_alloc, np.int64)
    row_ptr = np.zeros(n_alloc + 1, np.int32)
    np.cumsum(counts, out=row_ptr[1:])
    cap = quantize_rows(m + 1)  # >= 1 sentinel slot for the ELL pads
    sr = _semiring_of(kind)
    pad = cap - m
    slices, rank = _sliced_ell_index(dst, m, n_alloc, cap - 1, tuple(ell_cfg))
    plan_cfg = plan = None
    if kernel_plan is not None:
        from ..kernels.spmv import column_tile, edge_chunk
        chunk, bn = edge_chunk(kernel_plan[0], cap), column_tile(kernel_plan[1])
        plan = _tile_plan(dst, m, cap, n_alloc, chunk, bn)
        plan_cfg = (chunk, bn)
    src = np.concatenate([src, np.zeros(pad, np.int32)])
    dst = np.concatenate([dst, np.zeros(pad, np.int32)])
    val = np.concatenate([val, np.full(pad, sr.zero, val.dtype)])
    return CSRMatrix(
        row_ptr=jnp.asarray(row_ptr), col_idx=jnp.asarray(dst),
        edge_val=jnp.asarray(val), src_idx=jnp.asarray(src),
        ell_slices=tuple(jnp.asarray(t) for t in slices),
        ell_rank=jnp.asarray(rank), nnz=jnp.asarray(m, jnp.int32),
        tail_src=jnp.zeros(tail_min, jnp.int32),
        tail_dst=jnp.zeros(tail_min, jnp.int32),
        tail_val=jnp.full(tail_min, sr.zero, val.dtype),
        tail_ell=jnp.full((n_alloc, 1), tail_min - 1, jnp.int32),
        tail_nnz=jnp.asarray(0, jnp.int32),
        plan_tile=None if plan is None else jnp.asarray(plan[0]),
        plan_chunk=None if plan is None else jnp.asarray(plan[1]),
        plan_first=None if plan is None else jnp.asarray(plan[2]),
        n=n, n_alloc=n_alloc, kind=kind, ell_cfg=tuple(ell_cfg),
        plan_cfg=plan_cfg)


def tail_will_rebuild(csr: CSRMatrix, n_new: int,
                      rebuild_frac: float = 0.25) -> bool:
    """Would appending ``n_new`` arcs fold the COO tail into the spine?

    The one rebuild predicate shared by :func:`csr_append` and the serving
    layer (which re-runs the density heuristic at fold time — a tail that
    densified the graph may flip the carrier back to dense).  The absolute
    floor (8) only shields tiny spines from thrashing — the threshold must
    NOT track ``tail_capacity``, which re-quantizes upward on every append
    and would ratchet past ``rebuild_frac`` forever.
    """
    total_tail = int(csr.tail_nnz) + n_new
    return total_tail > max(rebuild_frac * max(int(csr.nnz), 1), 8)


def csr_append(csr: CSRMatrix, rows: np.ndarray,
               rebuild_frac: float = 0.25) -> CSRMatrix:
    """Monotone append: new arcs land in the COO tail; the CSR spine only
    rebuilds (re-sort + repack) when the tail outgrows ``rebuild_frac`` of
    the packed arcs, so the steady-state append is O(|ΔE|).  A rebuild
    carries the sliced-ELL config and tile-skip plan sizes forward (the
    autotuner's choices survive tail folds).

    Arcs must stay inside ``n_alloc`` — domain growth is the caller's rebuild
    (the serving layer re-allocates exactly like its dense twin).
    """
    if not csr.semiring.idempotent and len(rows):
        # set semantics on append too: a fact already in the spine/tail is a
        # no-op, not a second additive contribution (this also keeps the
        # counting increment-replay resume sound — Δ must be disjoint)
        have = {tuple(r) for r in csr.edges_numpy().tolist()}
        uniq = np.unique(np.asarray(rows, np.int64), axis=0)
        rows = np.asarray([r for r in uniq.tolist() if tuple(r) not in have],
                          np.int64).reshape(-1, 3)
    src, dst, val = _pack_edges(rows, csr.kind)
    if len(src) and int(max(src.max(), dst.max())) >= csr.n_alloc:
        raise ValueError("appended arcs outgrow n_alloc; rebuild the CSR")
    t = int(csr.tail_nnz)
    total_tail = t + len(src)
    if tail_will_rebuild(csr, len(src), rebuild_frac):
        merged = np.concatenate([csr.edges_numpy(),
                                 np.asarray(rows, np.int64).reshape(len(src), -1)])
        return build_csr(merged, csr.n_alloc, csr.kind, ell_cfg=csr.ell_cfg,
                         kernel_plan=csr.plan_cfg)
    cap = quantize_rows(total_tail + 1)  # >= 1 sentinel slot for the ELL pads
    sr = csr.semiring
    tsrc = np.full(cap, 0, np.int32)
    tdst = np.full(cap, 0, np.int32)
    tval = np.full(cap, sr.zero, np.asarray(csr.tail_val).dtype)
    tsrc[:t] = np.asarray(csr.tail_src[:t])
    tdst[:t] = np.asarray(csr.tail_dst[:t])
    tval[:t] = np.asarray(csr.tail_val[:t])
    tsrc[t:total_tail], tdst[t:total_tail], tval[t:total_tail] = src, dst, val
    tell = _ell_index(tdst, total_tail, csr.n_alloc, cap - 1)
    return dataclasses.replace(
        csr, tail_src=jnp.asarray(tsrc), tail_dst=jnp.asarray(tdst),
        tail_val=jnp.asarray(tval), tail_ell=jnp.asarray(tell),
        tail_nnz=jnp.asarray(total_tail, jnp.int32))


# ---------------------------------------------------------------------------
# Segment-semiring SpMV steps (the jnp oracle; Pallas twins in kernels/spmv)
# ---------------------------------------------------------------------------
# XLA lowers a lane scatter to a serialized per-index loop on CPU — the one
# formulation that would hand the O(|E|) advantage straight back.  The steps
# therefore run scatter-FREE: gather every arc's source value, then ⊕-reduce
# each slice's in-edge positions at the slice's own capacity, concatenate,
# and lane-gather back to vertex order through ``ell_rank``.  Work is
# O(B·(|E| + e_alloc)); every op is a dense gather/reduce the compiler
# vectorizes, and e_alloc tracks |E| instead of n·max_indeg.

#: widest single gather-⊕-reduce.  At batch widths of 16 and 32, XLA's TPU
#: compiler takes time that grows with the reduced width (one 2^20-wide hub
#: slice: ~150 s), so wider tables reduce in two stages of this width.
#: At 2^20 vertices that cut the bool fixpoint's compile from ~180 s to
#: ~11 s (v5e compiler, run on the host); the ⊕ is the same.
REDUCE_WIDTH = 1024


def _reduce_table(contrib: jax.Array, t, reduce) -> jax.Array:
    """``reduce(contrib[:, t], axis=2)``: the (B, rows) ⊕ over each row of
    packed positions ``t``, in two stages when ``t`` is wider than
    :data:`REDUCE_WIDTH`."""
    rows, w = t.shape
    if w <= REDUCE_WIDTH or w % REDUCE_WIDTH:
        return reduce(contrib[:, t], axis=2)
    k = w // REDUCE_WIDTH
    part = reduce(contrib[:, t.reshape(rows * k, REDUCE_WIDTH)], axis=2)
    return reduce(part.reshape(contrib.shape[0], rows, k), axis=2)


def _ell_step_or(f: jax.Array, src, val, ell) -> jax.Array:
    contrib = f[:, src] & val  # (B, cap): frontier value at each arc source
    return _reduce_table(contrib, ell, jnp.any)  # (B, n) over deg_cap


def _ell_step_min(f: jax.Array, src, val, ell) -> jax.Array:
    contrib = f[:, src] + val  # +inf sentinels never win the min
    return _reduce_table(contrib, ell, jnp.min)


def _sliced_step_or(f: jax.Array, src, val, slices, rank) -> jax.Array:
    contrib = f[:, src] & val
    parts = [_reduce_table(contrib, t, jnp.any) for t in slices]
    return jnp.concatenate(parts, axis=1)[:, rank]


def _sliced_step_min(f: jax.Array, src, val, slices, rank) -> jax.Array:
    contrib = f[:, src] + val
    parts = [_reduce_table(contrib, t, jnp.min) for t in slices]
    return jnp.concatenate(parts, axis=1)[:, rank]


def _ell_step_max(f: jax.Array, src, val, ell) -> jax.Array:
    contrib = f[:, src] + val  # -inf sentinels never win the max
    return _reduce_table(contrib, ell, jnp.max)


def _sliced_step_max(f: jax.Array, src, val, slices, rank) -> jax.Array:
    contrib = f[:, src] + val
    parts = [_reduce_table(contrib, t, jnp.max) for t in slices]
    return jnp.concatenate(parts, axis=1)[:, rank]


def _ell_step_sum(f: jax.Array, src, val, ell) -> jax.Array:
    contrib = f[:, src] * val  # 0-valued sentinels contribute nothing
    return _reduce_table(contrib, ell, jnp.sum)


def _sliced_step_sum(f: jax.Array, src, val, slices, rank) -> jax.Array:
    contrib = f[:, src] * val
    parts = [_reduce_table(contrib, t, jnp.sum) for t in slices]
    return jnp.concatenate(parts, axis=1)[:, rank]


def csr_frontier_or(frontier: jax.Array, csr: CSRMatrix) -> jax.Array:
    """One boolean frontier step over the packed arcs: O(B·|E|).

    ``frontier``: (B, n_alloc) bool (or (n_alloc,) — promoted).  Sentinel
    arcs carry ``val=False`` and never fire; the COO tail contributes a
    second (single-width) segment pass.
    """
    f = frontier[None, :] if frontier.ndim == 1 else frontier
    out = _sliced_step_or(f, csr.src_idx, csr.edge_val, csr.ell_slices,
                          csr.ell_rank)
    out = out | _ell_step_or(f, csr.tail_src, csr.tail_val, csr.tail_ell)
    return out[0] if frontier.ndim == 1 else out


def csr_frontier_min(frontier: jax.Array, csr: CSRMatrix) -> jax.Array:
    """One min-plus frontier step over the packed arcs (sentinels are +inf)."""
    f = frontier[None, :] if frontier.ndim == 1 else frontier
    out = _sliced_step_min(f, csr.src_idx, csr.edge_val, csr.ell_slices,
                           csr.ell_rank)
    out = jnp.minimum(
        out, _ell_step_min(f, csr.tail_src, csr.tail_val, csr.tail_ell))
    return out[0] if frontier.ndim == 1 else out


def csr_frontier_max(frontier: jax.Array, csr: CSRMatrix) -> jax.Array:
    """One max-plus frontier step over the packed arcs (sentinels are -inf)."""
    f = frontier[None, :] if frontier.ndim == 1 else frontier
    out = _sliced_step_max(f, csr.src_idx, csr.edge_val, csr.ell_slices,
                           csr.ell_rank)
    out = jnp.maximum(
        out, _ell_step_max(f, csr.tail_src, csr.tail_val, csr.tail_ell))
    return out[0] if frontier.ndim == 1 else out


def csr_frontier_sum(frontier: jax.Array, csr: CSRMatrix) -> jax.Array:
    """One plus-times frontier step over the packed arcs (sentinels are 0):
    the segment reduce IS an exact sum — parallel arcs both contribute."""
    f = frontier[None, :] if frontier.ndim == 1 else frontier
    out = _sliced_step_sum(f, csr.src_idx, csr.edge_val, csr.ell_slices,
                           csr.ell_rank)
    out = out + _ell_step_sum(f, csr.tail_src, csr.tail_val, csr.tail_ell)
    return out[0] if frontier.ndim == 1 else out


_FRONTIER_STEPS = {"bool": csr_frontier_or, "minplus": csr_frontier_min,
                   "maxplus": csr_frontier_max, "plustimes": csr_frontier_sum}


def csr_frontier_step(kind: str) -> Callable:
    """Module-level step for a carrier — stable identity for jit caches."""
    _semiring_of(kind)  # typed CarrierError on unknown kinds
    return _FRONTIER_STEPS[kind]


def rows_from_sources(csr: CSRMatrix, srcs) -> jax.Array:
    """The adjacency rows ``A[srcs]`` without materializing A: seed a ⊗-one
    one-hot frontier and take one segment step.  This is how the serving
    layer extracts batch seeds / append-resume deltas from a CSR relation.
    """
    return _rows_from_sources_jit(csr, jnp.asarray(srcs, jnp.int32))


@jax.jit
def _rows_from_sources_jit(csr: CSRMatrix, srcs: jax.Array) -> jax.Array:
    # one compiled program per (CSR buckets, batch width): run op by op,
    # the seed step would materialize every (B, slice) gather at once
    b = srcs.shape[0]
    sr = csr.semiring
    with jax.named_scope("csr_seed"):
        onehot = jnp.full((b, csr.n_alloc), sr.zero, sr.dtype)
        onehot = onehot.at[jnp.arange(b, dtype=jnp.int32), srcs].set(sr.one)
        return csr_frontier_step(csr.kind)(onehot, csr)


# ---------------------------------------------------------------------------
# Semi-naive frontier fixpoints over CSR (twin of fixpoint_dense form=vector)
# ---------------------------------------------------------------------------


def fixpoint_csr(csr: CSRMatrix, init: jax.Array, spmv: Callable | None = None,
                 max_iters: int | None = None) -> DenseResult:
    """Sparse frontier fixpoint: ``d <- d ⊕ step(Δ-masked d)`` to closure.

    Twin of ``fixpoint_dense(form="vector")`` over the packed arcs: ``init``
    is an (n_alloc,) or batched (B, n_alloc) frontier in the carrier; rows
    that converge drop out of the next segment step via the same per-row
    masking, and the same loop counts active rows and Δ entries.  Returns
    the same :class:`DenseResult` so callers (the serving batcher,
    ``Engine.ask_dense``) swap representations freely.
    """
    sr = csr.semiring
    step = spmv or csr_frontier_step(csr.kind)
    n = init.shape[-1]
    if max_iters is None:
        max_iters = additive_max_iters(n) if not sr.idempotent else 4 * n + 8

    def segment_step(d):
        with jax.named_scope("csr_segment_step"):
            return step(d, csr)

    if not sr.idempotent:
        # accumulate form (twin of fixpoint_dense form="accumulate"): the
        # idempotent convergence test is meaningless for additive ⊕, so the
        # delta propagates until it drains — bounded by max_iters, which the
        # host checks afterwards (check_additive_converged)
        return accumulate_fixpoint(sr, init, segment_step, max_iters)

    def masked_step(D, mask):
        rmask = mask if D.ndim == 1 else mask[:, None]
        return segment_step(jnp.where(rmask, D, jnp.asarray(sr.zero, D.dtype)))

    return masked_fixpoint(sr, init, masked_step, max_iters)


@functools.partial(jax.jit, static_argnames=("spmv", "max_iters"))
def _fixpoint_csr_jit(csr, init, spmv, max_iters):
    bump_trace_count()  # trace-time only: warm CSR batches must not move it
    return fixpoint_csr(csr, init, spmv=spmv, max_iters=max_iters)


def _default_max_iters(csr: CSRMatrix, init) -> int:
    n = init.shape[-1]
    return additive_max_iters(n) if not csr.semiring.idempotent \
        else 4 * n + 8


def fixpoint_csr_cached(csr: CSRMatrix, init: jax.Array,
                        spmv: Callable | None = None,
                        max_iters: int | None = None) -> DenseResult:
    """:func:`fixpoint_csr` under a shape-keyed jit (twin of
    ``fixpoint_dense_cached``): the CSR's bucketed capacities (slice shapes
    included) and the padded batch shape are the key, so warm serving
    batches skip re-tracing.  ``spmv`` must be a module-level callable for a
    stable cache key."""
    if max_iters is None:
        max_iters = _default_max_iters(csr, init)
    return _fixpoint_csr_jit(csr, init, spmv, max_iters)


def lower_fixpoint_csr(csr: CSRMatrix, init, spmv: Callable | None = None,
                       max_iters: int | None = None):
    """The ``jax.stages.Lowered`` program :func:`fixpoint_csr_cached` runs
    for these arguments, which may be ``jax.ShapeDtypeStruct`` leaves.
    ``.compile().memory_analysis()`` is what one launch needs on the device,
    without running it."""
    if max_iters is None:
        max_iters = _default_max_iters(csr, init)
    return _fixpoint_csr_jit.lower(csr, init, spmv, max_iters)


# convenience front-ends (mirror the dense ones) ------------------------------


def reachable_batch_csr(csr: CSRMatrix, srcs, spmv=None,
                        max_iters: int | None = None) -> DenseResult:
    """``?- tc(s, Y)`` for a batch of sources over packed arcs."""
    return fixpoint_csr_cached(csr, rows_from_sources(csr, srcs), spmv=spmv,
                               max_iters=max_iters)


def distances_batch_csr(csr: CSRMatrix, srcs, spmv=None,
                        max_iters: int | None = None) -> DenseResult:
    """``?- spath(s, Z, D)`` for a batch of sources (min-plus carrier)."""
    return fixpoint_csr_cached(csr, rows_from_sources(csr, srcs), spmv=spmv,
                               max_iters=max_iters)


def counts_batch_csr(csr: CSRMatrix, srcs, spmv=None,
                     max_iters: int | None = None) -> DenseResult:
    """``?- cpath(s, Z, C)`` for a batch of sources (plus-times carrier):
    accumulate-form over the packed arcs, host-checked against the additive
    iteration bound (:class:`~repro.core.seminaive.FixpointDivergenceError`
    on cyclic graphs)."""
    if max_iters is None:
        max_iters = additive_max_iters(csr.n_alloc)
    res = fixpoint_csr_cached(csr, rows_from_sources(csr, srcs), spmv=spmv,
                              max_iters=max_iters)
    return check_additive_converged(res, max_iters, "plus-times CSR batch")


# (de)serialization --------------------------------------------------------


def csr_to_state(csr: CSRMatrix) -> tuple[dict, dict]:
    """Flatten a :class:`CSRMatrix` to ``(arrays, meta)`` for the durable
    snapshot layer: ``arrays`` maps stable field names to host ndarrays
    (variable-count ``ell_slices`` become ``ell_slice_<i>``), ``meta`` is
    JSON-safe static metadata.  The round-trip through
    :func:`csr_from_state` is exact — COO tail contents, sliced-ELL layout
    (``ell_cfg``) and the tile-skip plan all survive, so a restored service
    resumes from bit-identical packed state instead of re-packing (and
    re-folding a live tail into the spine, which would change layout)."""
    arrays: dict[str, np.ndarray] = {}
    for name in ("row_ptr", "col_idx", "edge_val", "src_idx", "ell_rank",
                 "nnz", "tail_src", "tail_dst", "tail_val", "tail_ell",
                 "tail_nnz"):
        arrays[name] = np.asarray(getattr(csr, name))
    for i, t in enumerate(csr.ell_slices):
        arrays[f"ell_slice_{i}"] = np.asarray(t)
    if csr.plan_cfg is not None:
        for name in ("plan_tile", "plan_chunk", "plan_first"):
            arrays[name] = np.asarray(getattr(csr, name))
    meta = {"n": csr.n, "n_alloc": csr.n_alloc, "kind": csr.kind,
            "ell_cfg": list(csr.ell_cfg),
            "plan_cfg": list(csr.plan_cfg) if csr.plan_cfg else None,
            "n_slices": len(csr.ell_slices)}
    return arrays, meta


def csr_from_state(arrays: dict, meta: dict) -> CSRMatrix:
    """Inverse of :func:`csr_to_state` (arrays land back on device)."""
    j = {k: jnp.asarray(v) for k, v in arrays.items()
         if not k.startswith("ell_slice_")}
    slices = tuple(jnp.asarray(arrays[f"ell_slice_{i}"])
                   for i in range(int(meta["n_slices"])))
    plan_cfg = tuple(meta["plan_cfg"]) if meta.get("plan_cfg") else None
    return CSRMatrix(
        row_ptr=j["row_ptr"], col_idx=j["col_idx"], edge_val=j["edge_val"],
        src_idx=j["src_idx"], ell_slices=slices, ell_rank=j["ell_rank"],
        nnz=j["nnz"], tail_src=j["tail_src"], tail_dst=j["tail_dst"],
        tail_val=j["tail_val"], tail_ell=j["tail_ell"],
        tail_nnz=j["tail_nnz"],
        plan_tile=j.get("plan_tile"), plan_chunk=j.get("plan_chunk"),
        plan_first=j.get("plan_first"),
        n=int(meta["n"]), n_alloc=int(meta["n_alloc"]),
        kind=str(meta["kind"]), ell_cfg=tuple(meta["ell_cfg"]),
        plan_cfg=plan_cfg)
