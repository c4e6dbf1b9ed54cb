"""Distributed evaluation plans (shard_map) — §6/§7 of the paper on a mesh.

Three plans, mirroring the paper's taxonomy:

``tc_decomposable``   Figure 4: the recursive relation row-sharded on its GPS
    (first argument), the base relation broadcast once; the fixpoint body has
    **zero collectives** except the scalar convergence ``psum``.  This is the
    plan that let BigDatalog beat GraphX; here the per-iteration join is a
    semiring matmul on each shard's rows.

``sg_allreduce``      Figures 2(b)/3: same-generation is not decomposable; the
    sandwich contraction Aᵀ(SA) needs one ``psum`` (all-reduce) per iteration
    — the collective playing the role of Spark's shuffle.

``psn_shuffle_agg``   §7.1 Example 12 generalized: tuple-level PSN where each
    worker owns the hash partition of the recursive relation given by its
    discriminating set; derived tuples are re-keyed and exchanged with
    ``all_to_all`` each iteration (the message-passing PSN of the related
    work, realized as one fused collective).

All three carry monotone state, so restart/replay is idempotent (the SetRDD
argument).  Each returns (result, iterations) and is jit-compatible; the
dry-run lowers them on the production mesh to prove the sharding is coherent.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .relation import EMPTY, hash32
from .semiring import BOOL, MIN_PLUS, Semiring


# ---------------------------------------------------------------------------
# Dense decomposable TC / SSSP (GPS = first argument)
# ---------------------------------------------------------------------------


def _changed(sr: Semiring, dn: jax.Array, d: jax.Array) -> jax.Array:
    """1 when this shard's rows moved, as int32: the convergence test folds
    to one bool per shard before the psum, so no 64-bit count of the (B, n)
    rows is ever formed (the chip emulates 64-bit arithmetic)."""
    moved = dn != d if sr.dtype == jnp.bool_ else \
        ~((dn == d) | (jnp.isinf(dn) & jnp.isinf(d)))
    return jnp.any(moved).astype(jnp.int32)


def tc_decomposable(mesh, adj: jax.Array, axis: str = "data",
                    sr: Semiring = BOOL, matmul=None, max_iters: int | None = None,
                    init: jax.Array | None = None):
    """Row-sharded semiring fixpoint with a shuffle-free recursion.

    adj: (n, n) dense relation in the semiring's carrier (bool for TC,
    float32 +inf-padded for shortest-distance).  ``init`` overrides the
    fixpoint seed (default: adj itself = the all-pairs closure); a
    magic-restricted query seeds only its frontier rows instead (see
    :func:`tc_frontier_decomposable`).  Returns (closure, iters).
    """
    mm = matmul or sr.matmul
    n = adj.shape[0]
    iters_cap = max_iters or (4 * n + 8)

    def body_fn(d_loc, arc_full):
        # d_loc: (n/k, n) local rows; arc_full: (n, n) broadcast base relation

        def cond(c):
            _, alive, it = c
            return alive & (it < iters_cap)

        def body(c):
            d, _, it = c
            upd = mm(d, arc_full)
            dn = sr.add(d, upd)
            # global convergence: the only collective in the loop
            alive = jax.lax.psum(_changed(sr, dn, d), axis) > 0
            return dn, alive, it + 1

        d, _, it = jax.lax.while_loop(cond, body, (d_loc, jnp.array(True), jnp.int32(0)))
        return d, it

    fn = jax.shard_map(
        body_fn, mesh=mesh,
        in_specs=(P(axis, None), P()),  # rows sharded; arc broadcast (Fig. 4)
        out_specs=(P(axis, None), P()),
        check_vma=False,
    )
    return fn(adj if init is None else init, adj)


def spath_decomposable(mesh, w: jax.Array, axis: str = "data", matmul=None):
    """All-pairs shortest paths, decomposable plan (Example 2 distributed)."""
    return tc_decomposable(mesh, w, axis, MIN_PLUS, matmul)


def tc_frontier_decomposable(mesh, adj: jax.Array, frontier: jax.Array,
                             axis: str = "data", sr: Semiring = BOOL,
                             matmul=None, max_iters: int | None = None):
    """Magic-restricted decomposable plan: close only the query's frontier.

    ``frontier``: (k, n) seed rows in the semiring carrier — for
    ``?- tc(s, Y)`` the single row ``adj[s]``; for a multi-source query one
    row per source.  The k frontier rows are sharded exactly like the full
    recursive relation in Fig. 4 (the GPS pivot is the source argument), so
    the recursion stays shuffle-free; rows are zero-padded to a multiple of
    the mesh axis and sliced back after the fixpoint.
    """
    k = frontier.shape[0]
    nshards = mesh.shape[axis]
    pad = (-k) % nshards
    if pad:
        fill = jnp.full((pad, frontier.shape[1]), sr.zero, frontier.dtype)
        frontier = jnp.concatenate([frontier, fill])
    closed, iters = tc_decomposable(mesh, adj, axis, sr, matmul, max_iters,
                                    init=frontier)
    return closed[:k], iters


@functools.partial(jax.jit,
                   static_argnames=("mesh", "axis", "spmv", "max_iters"))
def csr_frontier_decomposable(mesh, csr, frontier: jax.Array,
                              axis: str = "data", spmv=None,
                              max_iters: int | None = None):
    """Fig.-4 sharding of the *sparse* frontier fixpoint (``core.sparse``).

    The (B, n) batch frontier rows shard across the mesh exactly like
    ``tc_frontier_decomposable`` (the GPS pivot is the source argument); the
    CSR-packed arcs broadcast once, like the base relation, so each shard
    runs its own O(|E|)-per-iteration segment fixpoint and the recursion
    stays shuffle-free — the only collective is the scalar convergence
    ``psum``.  Rows zero-pad to a multiple of the axis size and slice back.
    Jitted with the mesh static, so warm batch shapes reuse one program.
    """
    from .sparse import csr_frontier_step

    sr = csr.semiring
    step = spmv or csr_frontier_step(csr.kind)
    k = frontier.shape[0]
    nshards = mesh.shape[axis]
    pad = (-k) % nshards
    if pad:
        fill = jnp.full((pad, frontier.shape[1]), sr.zero, frontier.dtype)
        frontier = jnp.concatenate([frontier, fill])
    iters_cap = max_iters or (4 * frontier.shape[1] + 8)

    def body_fn(f_loc, csr_full):
        def cond(c):
            _, alive, it = c
            return alive & (it < iters_cap)

        def body(c):
            d, _, it = c
            upd = step(d, csr_full)
            dn = sr.add(d, upd)
            # the only collective
            alive = jax.lax.psum(_changed(sr, dn, d), axis) > 0
            return dn, alive, it + 1

        d, _, it = jax.lax.while_loop(
            cond, body, (f_loc, jnp.array(True), jnp.int32(0)))
        return d, it

    fn = jax.shard_map(
        body_fn, mesh=mesh,
        in_specs=(P(axis, None), P()),  # rows sharded; packed arcs broadcast
        out_specs=(P(axis, None), P()),
        check_vma=False,
    )
    closed, iters = fn(frontier, csr)
    return closed[:k], iters


def resume_frontier_decomposable(mesh, adj: jax.Array, prev: jax.Array,
                                 seed: jax.Array, axis: str = "data",
                                 sr: Semiring = BOOL, matmul=None,
                                 max_iters: int | None = None):
    """Resume a sharded frontier fixpoint after a monotone EDB append.

    The state is monotone (SetRDD argument), so restarting the Fig.-4 loop
    from ``prev ⊕ seed`` — the previously closed frontier rows joined with
    the post-append seed rows for the same sources — converges to the new
    closure over the appended ``adj`` in as many iterations as the *delta*
    needs, not the full recursion depth.  This is the distributed twin of the
    serving layer's ``repro.service.incremental`` path.
    """
    return tc_frontier_decomposable(mesh, adj, sr.add(prev, seed), axis, sr,
                                    matmul, max_iters)


# ---------------------------------------------------------------------------
# SG: sandwich plan with one all-reduce per iteration
# ---------------------------------------------------------------------------


def sg_allreduce(mesh, adj: jax.Array, axis: str = "data", max_iters: int | None = None):
    n = adj.shape[0]
    iters_cap = max_iters or (2 * n + 8)
    nshards = mesh.shape[axis]

    def body_fn(a_loc):
        # a_loc: (n/k, n) local rows of adj
        idx = jax.lax.axis_index(axis)
        rows = n // nshards
        row0 = idx * rows

        def to_f(x):
            return x.astype(jnp.float32)

        # exit rule: sg0 = AᵀA \ id, rows sharded. (AᵀA)[x, y] needs column
        # slices of A -> contraction over global rows: partial + psum.
        part = jnp.matmul(to_f(a_loc).T, to_f(a_loc))  # (n, n) partial
        sg_full = jax.lax.psum(part, axis) > 0
        eye = jnp.zeros((rows, n), bool).at[jnp.arange(rows), row0 + jnp.arange(rows)].set(True)
        sg_loc = jax.lax.dynamic_slice_in_dim(sg_full, row0, rows, 0) & ~eye

        def cond(c):
            _, alive, it = c
            return alive & (it < iters_cap)

        def body2(c):
            s, _, it = c
            sa = jnp.matmul(to_f(s), ga)  # local rows of (S A)
            part = jnp.matmul(a_loc_f.T, sa)  # contraction over my rows of A
            new_full = jax.lax.psum(part, axis) > 0  # all-reduce == shuffle
            # no diagonal mask here: only the exit rule carries X != Y
            new_loc = jax.lax.dynamic_slice_in_dim(new_full, row0, rows, 0)
            sn = s | new_loc
            alive = jax.lax.psum(jnp.sum(sn != s), axis) > 0
            return sn, alive, it + 1

        a_loc_f = to_f(a_loc)
        ga = to_f(jax.lax.all_gather(a_loc, axis, tiled=True))  # broadcast arc once
        s, _, it = jax.lax.while_loop(cond, body2, (sg_loc, jnp.array(True), jnp.int32(0)))
        return s, it

    fn = jax.shard_map(body_fn, mesh=mesh, in_specs=P(axis, None),
                       out_specs=(P(axis, None), P()), check_vma=False)
    return fn(adj)


# ---------------------------------------------------------------------------
# Tuple-level distributed PSN with all_to_all shuffle (Example 12 generalized)
# ---------------------------------------------------------------------------


def _bucket_by_dest(keys: jax.Array, vals: jax.Array | None, dest: jax.Array,
                    n_dest: int, bucket_cap: int):
    """Scatter (key, val) pairs into per-destination buckets (n_dest, cap)."""
    dest = jnp.where(keys == EMPTY, n_dest - 1, dest)  # park empties anywhere
    order = jnp.argsort(dest * 2 + (keys == EMPTY))  # valid first per dest
    ks, ds = keys[order], dest[order]
    vs = vals[order] if vals is not None else None
    start = jnp.searchsorted(ds, jnp.arange(n_dest))
    rank = jnp.arange(ks.shape[0]) - start[ds]
    ok = (rank < bucket_cap) & (ks != EMPTY)
    buckets = jnp.full((n_dest, bucket_cap), EMPTY, jnp.int64)
    buckets = buckets.at[jnp.where(ok, ds, 0), jnp.where(ok, rank, 0)].set(
        jnp.where(ok, ks, buckets[0, 0]), mode="drop")
    vbuckets = None
    if vs is not None:
        vbuckets = jnp.zeros((n_dest, bucket_cap), vs.dtype)
        vbuckets = vbuckets.at[jnp.where(ok, ds, 0), jnp.where(ok, rank, 0)].set(
            jnp.where(ok, vs, 0), mode="drop")
    overflow = jnp.any((rank >= bucket_cap) & (ks != EMPTY))
    return buckets, vbuckets, overflow


def psn_shuffle_agg(
    mesh,
    edges: jax.Array,  # (m, 2) int64 arcs, hash-partitioned by src outside
    init_keys: jax.Array,  # (cap,) per-shard initial agg keys (vertex ids)
    init_vals: jax.Array,  # (cap,) initial values (e.g. own label)
    n_vertices: int,
    axis: str = "data",
    kind: str = "min",
    max_iters: int = 1 << 14,
    bucket_cap: int | None = None,
):
    """Distributed label-propagation-style PSN (CC / single-source distances).

    State per shard: AggTable-like (vertex -> value) for vertices hashed to
    this shard.  Each iteration: join local delta against local arcs (arcs are
    partitioned by src with the same hash), produce (dst, value) candidates,
    ``all_to_all``-shuffle them to the owner of dst, ⊕-merge, repeat.
    """
    from .relation import AggTable

    nshards = mesh.shape[axis]
    cap = init_keys.shape[0]
    bcap = bucket_cap or cap

    merge = jnp.minimum if kind == "min" else jnp.maximum

    def body_fn(edges_loc, keys0, vals0):
        src, dst = edges_loc[:, 0], edges_loc[:, 1]
        esort = jnp.argsort(src)
        src_s, dst_s = src[esort], dst[esort]

        def relax(dkeys, dvals):
            # join delta (vertex -> value) with local arcs on src
            lo = jnp.searchsorted(src_s, dkeys, side="left")
            hi = jnp.searchsorted(src_s, dkeys, side="right")
            m = jnp.where(dkeys != EMPTY, hi - lo, 0)
            off = jnp.cumsum(m)
            total = off[-1]
            starts = off - m
            slot = jnp.arange(bcap * nshards)
            pi = jnp.clip(jnp.searchsorted(off, slot, side="right"), 0, dkeys.shape[0] - 1)
            rank = slot - starts[pi]
            ei = jnp.clip(lo[pi] + rank, 0, src_s.shape[0] - 1)
            ok = slot < jnp.minimum(total, slot.shape[0])
            out_k = jnp.where(ok, dst_s[ei].astype(jnp.int64), EMPTY)
            out_v = jnp.where(ok, dvals[pi], 0)
            return out_k, out_v, total > slot.shape[0]

        def cond(c):
            _, _, _, _, alive, it, _ = c
            return alive & (it < max_iters)

        def body(c):
            keys, vals, dkeys, dvals, _, it, ovf = c
            ck, cv, o1 = relax(dkeys, dvals)
            dest = hash32(ck, nshards)
            bk, bv, o2 = _bucket_by_dest(ck, cv, dest, nshards, bcap)
            rk = jax.lax.all_to_all(bk, axis, 0, 0, tiled=True).reshape(-1)
            rv = jax.lax.all_to_all(bv, axis, 0, 0, tiled=True).reshape(-1)
            # ⊕-merge into local table
            t = AggTable(keys=keys, values=vals, incs=vals,
                         count=jnp.sum(keys != EMPTY).astype(jnp.int32),
                         overflow=jnp.zeros((), bool), kind=kind)
            nt, dt = t.merge(rk, rv)
            alive = jax.lax.psum(dt.count, axis) > 0
            return (nt.keys, nt.values, dt.keys, dt.values, alive, it + 1,
                    ovf | o1 | o2 | nt.overflow)

        init = (keys0, vals0, keys0, vals0, jnp.array(True), jnp.int32(0),
                jnp.zeros((), bool))
        keys, vals, _, _, _, it, ovf = jax.lax.while_loop(cond, body, init)
        return keys, vals, it, ovf

    fn = jax.shard_map(
        body_fn, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(), P()),
        check_vma=False,
    )
    return fn(edges, init_keys, init_vals)


def partition_edges_by_src(edges, n_shards, cap_per_shard):
    """Host-side helper: hash-partition an edge list by source vertex.

    Fully vectorized (stable argsort by destination shard + rank-in-shard
    scatter): the previous per-edge Python loop cost O(m) interpreter time,
    which dominated setup on million-edge inputs.  Unused slots are parked on
    an off-domain sentinel self-loop that owns no label.
    """
    import numpy as np

    edges = np.asarray(edges, np.int64).reshape((-1, 2))
    h = ((edges[:, 0].astype(np.uint64) * np.uint64(11400714819323198485))
         >> np.uint64(40)) % np.uint64(n_shards)
    dest = h.astype(np.int64)
    counts = np.bincount(dest, minlength=n_shards)
    if counts.size and counts.max() > cap_per_shard:
        raise ValueError("edge partition overflow; raise cap_per_shard")
    order = np.argsort(dest, kind="stable")
    sorted_dest = dest[order]
    starts = np.cumsum(counts) - counts  # first slot of each shard's run
    rank = np.arange(len(edges)) - starts[sorted_dest]
    out = np.full((n_shards, cap_per_shard, 2), 1 << 40, np.int64)
    out[sorted_dest, rank] = edges[order]
    return out.reshape(n_shards * cap_per_shard, 2)
