"""Per-kernel allclose vs the pure-jnp oracles (interpret mode), with
shape/dtype sweeps via hypothesis over the blockable shape lattice."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_stub import given, settings, st

from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


def rand_dist(m, n, p=0.25):
    return jnp.asarray(np.where(RNG.random((m, n)) < p,
                                RNG.integers(1, 9, (m, n)), np.inf), jnp.float32)


DIMS = st.sampled_from([64, 128, 192, 256])


@given(DIMS, DIMS, DIMS)
@settings(max_examples=6, deadline=None)
def test_minplus_shapes(m, k, n):
    a, b = rand_dist(m, k), rand_dist(k, n)
    out = ops.minplus(a, b, bm=64, bn=64, bk=32)
    assert jnp.array_equal(out, ref.minplus_ref(a, b))


@given(DIMS, DIMS, DIMS)
@settings(max_examples=6, deadline=None)
def test_boolmm_shapes(m, k, n):
    a = jnp.asarray(RNG.random((m, k)) < 0.1)
    b = jnp.asarray(RNG.random((k, n)) < 0.1)
    assert jnp.array_equal(ops.boolmm(a, b, bm=64, bn=64, bk=64),
                           ref.boolmm_ref(a, b))


@pytest.mark.parametrize("n", [128, 256])
def test_relax_fused(n):
    d = rand_dist(n, n, 0.2)
    a = rand_dist(n, n, 0.05)
    mask = jnp.asarray(RNG.random(n) < 0.5)
    dn, ch = ops.relax(d, a, mask, bm=64, bn=64, bk=32)
    dn2, ch2 = ref.relax_ref(d, a, mask)
    assert jnp.array_equal(dn, dn2) and jnp.array_equal(ch, ch2)


def test_relax_drives_sssp_fixpoint():
    """Iterating the fused kernel IS the PreM-optimized PSN loop."""
    n = 128
    arc = rand_dist(n, n, 0.03)
    d = arc
    mask = jnp.ones(n, bool)
    for _ in range(n):
        d, mask = ops.relax(d, arc, mask, bm=64, bn=64, bk=32)
        if not bool(mask.any()):
            break
    # oracle: repeated dense min-plus
    want = arc
    while True:
        new = jnp.minimum(want, ref.minplus_ref(want, arc))
        if jnp.array_equal(new, want):
            break
        want = new
    assert jnp.array_equal(d, want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kw", [
    dict(causal=True), dict(causal=True, window=64),
    dict(causal=True, softcap=30.0), dict(causal=False),
])
def test_flash_attention_variants(kw, dtype):
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 256, 64), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 256, 64), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 2, 256, 64), dtype)
    o = ops.flash(q, k, v, **kw)
    w = ref.flash_attention_ref(q, k, v, **kw)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    assert float(jnp.max(jnp.abs(o.astype(jnp.float32) - w.astype(jnp.float32)))) < tol


@given(st.sampled_from([1, 2]), st.sampled_from([256, 512]),
       st.sampled_from([128, 256]))
@settings(max_examples=4, deadline=None)
def test_rglru_scan_shapes(b, s, w):
    a = jax.random.uniform(jax.random.PRNGKey(3), (b, s, w), jnp.float32, 0.5, 0.99)
    x = jax.random.normal(jax.random.PRNGKey(4), (b, s, w), jnp.float32)
    h = ops.rglru(a, x, bw=128, bs=128)
    hr = ref.rglru_scan_ref(a, x)
    assert float(jnp.max(jnp.abs(h - hr))) < 1e-4


def test_kernel_backed_dense_engine():
    """The dense fixpoint engine accepts the Pallas ⊗ as a drop-in."""
    from repro.core.seminaive import transitive_closure_dense
    n = 128
    adj = jnp.asarray(RNG.random((n, n)) < 0.03)
    res_ref = transitive_closure_dense(adj)
    res_k = transitive_closure_dense(
        adj, matmul=lambda a, b: ops.boolmm(a, b, bm=64, bn=64, bk=64))
    assert jnp.array_equal(res_ref.table, res_k.table)


@given(st.sampled_from([1, 3, 8, 17]), st.sampled_from([50, 128, 200]))
@settings(max_examples=6, deadline=None)
def test_bool_frontier_padding(b, n):
    """The serving batch ⊗: ragged (B, n) pads to tiles with ⊕-zeros."""
    f = jnp.asarray(RNG.random((b, n)) < 0.2)
    adj = jnp.asarray(RNG.random((n, n)) < 0.1)
    want = jnp.matmul(f.astype(jnp.float32), adj.astype(jnp.float32)) > 0
    assert jnp.array_equal(ops.bool_frontier(f, adj), want)


@given(st.sampled_from([1, 3, 8, 17]), st.sampled_from([50, 128, 200]))
@settings(max_examples=6, deadline=None)
def test_minplus_frontier_padding(b, n):
    """Pad lanes are +inf: they must never win a min over real entries."""
    f = rand_dist(b, n, 0.3)
    w = rand_dist(n, n, 0.1)
    assert jnp.array_equal(ops.minplus_frontier(f, w), ref.minplus_ref(f, w))


def test_frontier_matmul_drives_batched_fixpoint():
    """The padded frontier kernels are drop-in ⊗ for the batched serving
    fixpoint (the matmul='pallas' service path)."""
    from repro.core.seminaive import reachable_batch_dense
    n = 100
    adj = jnp.asarray(RNG.random((n, n)) < 0.04)
    srcs = [0, 7, 63]
    res_ref = reachable_batch_dense(adj, srcs)
    res_k = reachable_batch_dense(adj, srcs, matmul=ops.bool_frontier)
    assert jnp.array_equal(res_ref.table, res_k.table)


# ---------------------------------------------------------------------------
# CSR segment-semiring SpMV (the sparse serving hot path)
# ---------------------------------------------------------------------------


def _rand_csr(n, p, kind, seed=0):
    from repro.core.sparse import build_csr
    rng = np.random.default_rng(seed)
    mask = rng.random((n, n)) < p
    np.fill_diagonal(mask, False)
    src, dst = np.nonzero(mask)
    edges = np.stack([src, dst], axis=1).astype(np.int64)
    if kind == "minplus":
        edges = np.concatenate(
            [edges, rng.integers(1, 9, (len(edges), 1))], axis=1)
    return build_csr(edges, n, kind), edges


@given(st.sampled_from([1, 3, 8]), st.sampled_from([64, 100, 192]),
       st.sampled_from([0.02, 0.1]))
@settings(max_examples=6, deadline=None)
def test_csr_bool_spmv_vs_dense(b, n, p):
    """Segment-OR over packed arcs == dense bool matmul (one-hot MXU
    scatter; sentinel pad arcs carry val=False and never fire)."""
    csr, edges = _rand_csr(n, p, "bool", seed=n + b)
    adj = np.zeros((n, n), np.float32)
    adj[edges[:, 0], edges[:, 1]] = 1.0
    f = RNG.random((b, n)) < 0.2
    want = jnp.asarray((f.astype(np.float32) @ adj) > 0)
    got = ops.csr_bool(jnp.asarray(f), csr.src_idx, csr.col_idx, csr.edge_val)
    assert jnp.array_equal(got, want)


@given(st.sampled_from([1, 3, 8]), st.sampled_from([64, 100]),
       st.sampled_from([0.02, 0.1]))
@settings(max_examples=6, deadline=None)
def test_csr_minplus_spmv_vs_dense(b, n, p):
    """Segment-min over packed arcs == dense min-plus product (masked
    broadcast-min over column tiles; +inf sentinels never win)."""
    csr, edges = _rand_csr(n, p, "minplus", seed=n + b)
    w = np.full((n, n), np.inf, np.float32)
    np.minimum.at(w, (edges[:, 0], edges[:, 1]), edges[:, 2].astype(np.float32))
    f = np.asarray(rand_dist(b, n, 0.3))
    want = ref.minplus_ref(jnp.asarray(f), jnp.asarray(w))
    got = ops.csr_minplus(jnp.asarray(f), csr.src_idx, csr.col_idx,
                          csr.edge_val)
    assert jnp.array_equal(got, want)


def test_csr_kernel_steps_match_jnp_segment_path():
    """The Pallas steps agree with the jnp gather/scatter oracle in
    ``core.sparse`` — spine AND COO tail."""
    from repro.core import sparse
    csr, _ = _rand_csr(96, 0.05, "bool", seed=5)
    csr = sparse.csr_append(csr, np.array([[0, 95], [95, 3]], np.int64))
    assert int(csr.tail_nnz) > 0  # the tail pass is actually exercised
    f = jnp.asarray(RNG.random((8, 96)) < 0.2)
    assert jnp.array_equal(ops.csr_frontier_step("bool")(f, csr),
                           sparse.csr_frontier_or(f, csr))
    csr_w, _ = _rand_csr(96, 0.05, "minplus", seed=6)
    fw = jnp.asarray(np.asarray(rand_dist(4, 96, 0.3)))
    assert jnp.array_equal(ops.csr_frontier_step("minplus")(fw, csr_w),
                           sparse.csr_frontier_min(fw, csr_w))


def test_csr_kernel_drives_sparse_fixpoint():
    """The kernel-backed step is a drop-in spmv for ``fixpoint_csr`` (the
    matmul='pallas' service path on a CSR relation) and both agree with the
    dense closure."""
    from repro.core import sparse
    from repro.core.seminaive import reachable_batch_dense
    csr, edges = _rand_csr(80, 0.04, "bool", seed=9)
    adj = np.zeros((80, 80), bool)
    adj[edges[:, 0], edges[:, 1]] = True
    srcs = [0, 7, 63]
    init = sparse.rows_from_sources(csr, srcs)
    res_j = sparse.fixpoint_csr(csr, init)
    res_k = sparse.fixpoint_csr(csr, init, spmv=ops.csr_frontier_step("bool"))
    want = reachable_batch_dense(jnp.asarray(adj), srcs)
    assert jnp.array_equal(res_j.table, want.table)
    assert jnp.array_equal(res_k.table, want.table)


# -- sliced-ELL / tile-skip additions (ROADMAP item 6) ----------------------


def test_csr_minplus_spmv_pads_odd_widths():
    """Regression: frontier widths that don't divide bn used to trip a hard
    assert; the wrapper now pads (pad columns masked out of the min)."""
    from repro.core import sparse
    for n in (100, 130, 200):
        csr, edges = _rand_csr(n, 0.05, "minplus", seed=n)
        w = np.full((n, n), np.inf, np.float32)
        np.minimum.at(w, (edges[:, 0], edges[:, 1]),
                      edges[:, 2].astype(np.float32))
        f = np.asarray(rand_dist(3, n, 0.3))
        want = ref.minplus_ref(jnp.asarray(f), jnp.asarray(w))
        for bn in (64, 96, 128, 256):
            got = ops.csr_minplus(jnp.asarray(f), csr.src_idx, csr.col_idx,
                                  csr.edge_val, bn=bn)
            assert jnp.array_equal(got, want), (n, bn)


def test_csr_minplus_tiled_matches_untiled():
    """The scalar-prefetch tile-skip kernel == the dense-grid kernel == the
    jnp oracle, across plan block sizes."""
    from repro.core import sparse
    n = 128
    csr0, edges = _rand_csr(n, 0.04, "minplus", seed=3)
    w = np.full((n, n), np.inf, np.float32)
    np.minimum.at(w, (edges[:, 0], edges[:, 1]), edges[:, 2].astype(np.float32))
    f = jnp.asarray(np.asarray(rand_dist(4, n, 0.3)))
    want = ref.minplus_ref(f, jnp.asarray(w))
    for chunk, bn in ((32, 128), (16, 64), (64, 128)):
        csr = sparse.build_csr(edges, n, "minplus", kernel_plan=(chunk, bn))
        assert csr.plan_cfg is not None and csr.plan_tile is not None
        got = ops.csr_minplus_tiled(
            f, csr.src_idx, csr.col_idx, csr.edge_val, csr.plan_tile,
            csr.plan_chunk, csr.plan_first, chunk=csr.plan_cfg[0],
            bn=csr.plan_cfg[1])
        assert jnp.array_equal(got, want), (chunk, bn)


def test_tiled_kernel_drives_fixpoint_with_tail():
    """A planned CSR + COO tail routed through ``csr_frontier_step`` (the
    tile-skip spine pass + untiled tail pass) reaches the same closure."""
    from repro.core import sparse
    from repro.core.seminaive import distances_batch_dense
    n = 96
    csr0, edges = _rand_csr(n, 0.05, "minplus", seed=11)
    csr = sparse.build_csr(edges, n, "minplus", kernel_plan=(32, 128))
    csr = sparse.csr_append(csr, np.array([[0, 95, 2], [95, 1, 3]], np.int64))
    assert int(csr.tail_nnz) > 0
    w = np.full((n, n), np.inf, np.float32)
    np.minimum.at(w, (edges[:, 0], edges[:, 1]), edges[:, 2].astype(np.float32))
    w[0, 95] = min(w[0, 95], 2.0)
    w[95, 1] = min(w[95, 1], 3.0)
    srcs = [0, 9, 40]
    got = sparse.distances_batch_csr(csr, srcs,
                                     spmv=ops.csr_frontier_step("minplus"))
    want = distances_batch_dense(jnp.asarray(w), srcs)
    assert jnp.array_equal(got.table, want.table)


def test_bool_chunk_skip_inactive_frontier():
    """The bool kernel's per-chunk activity prefetch: a frontier touching no
    arc source must yield all-False, and partial activity must not drop
    contributions (oracle equality on a hub graph)."""
    from repro.core import sparse
    from repro.data.graphs import powerlaw_graph
    edges = powerlaw_graph(96, 300, seed=2)
    csr = sparse.build_csr(edges, 128, "bool")
    dead = np.zeros((4, 128), bool)  # no live sources at all
    got = ops.csr_bool(jnp.asarray(dead), csr.src_idx, csr.col_idx,
                       csr.edge_val)
    assert not bool(jnp.any(got))
    adj = np.zeros((128, 128), np.float32)
    adj[edges[:, 0], edges[:, 1]] = 1.0
    part = RNG.random((4, 128)) < 0.05  # sparse frontier: most chunks skip
    want = jnp.asarray((part.astype(np.float32) @ adj) > 0)
    got = ops.csr_bool(jnp.asarray(part), csr.src_idx, csr.col_idx,
                       csr.edge_val)
    assert jnp.array_equal(got, want)


def test_autotune_pinned_and_measured():
    """Pinned configs skip measurement; a measured search on a heavy-tailed
    graph prefers a sliced ladder over single-width and caches by shape."""
    from repro.data.graphs import powerlaw_graph
    from repro.kernels import autotune as at
    edges = powerlaw_graph(256, 1500, alpha=1.5, seed=4)
    cfg = at.KernelConfig(slice_floor=2, slice_stride=1)
    csr = at.build_tuned(edges, 256, "bool", cfg)
    assert csr.ell_cfg == (2, 1) and csr.plan_cfg is None
    at.clear_cache()
    res = at.autotune(edges, 256, "bool", include_kernels=False)
    assert not res.cached and res.gain > 0
    assert res.config.slice_stride > 0, \
        "heavy-tail search should not pick single-width"
    assert any(c["measured_s"] is None for c in res.candidates), \
        "analytic seed should prune at least one candidate"
    res2 = at.autotune(edges, 256, "bool", include_kernels=False)
    assert res2.cached and res2.config == res.config


def test_autotune_records_skipped_kernel_candidates():
    """Kernel candidates are never dropped in silence: off a TPU they are
    listed as not measured, and at a width their VMEM arithmetic refuses
    they are listed with the bytes they would need."""
    from repro.data.graphs import powerlaw_graph
    from repro.kernels import autotune as at
    edges = powerlaw_graph(256, 1500, alpha=1.5, seed=4)
    at.clear_cache()
    res = at.autotune(edges, 256, "bool", use_cache=False)
    kern = [c for c in res.candidates if c["config"].use_kernel]
    assert kern and all(c["measured_s"] is None for c in kern)
    assert all("backend cpu" in c["skipped"] for c in kern)
    assert res.frac_peak_flops is None and res.frac_peak_bw is None
    wide = 1 << 17  # (64, 2^17) bool outputs overflow the scoped VMEM
    res = at.autotune(edges, wide, "bool", batch=64, include_kernels=True,
                      use_cache=False)
    kern = [c for c in res.candidates if c["config"].use_kernel]
    assert kern and all(c["measured_s"] is None for c in kern)
    assert all("bytes of VMEM" in c["skipped"] for c in kern)
    assert not res.config.use_kernel


def test_autotune_guards_kernels_at_the_launch_width():
    """The VMEM guard uses the widest width the relation is launched at: a
    bool kernel that fits 8 rows at 2^15 columns but not 128 rows is
    skipped when tuning for 128, and the tuned service tunes for its widest
    batch pad, which the admission ``max_batch`` sets."""
    from repro.data.graphs import powerlaw_graph
    from repro.kernels import autotune as at
    from repro.kernels.spmv import VMEM_LIMIT_BYTES, vmem_bytes
    from repro.service import AsyncDatalogService, DatalogService
    n = 1 << 15
    assert vmem_bytes("bool", 8, n) <= VMEM_LIMIT_BYTES \
        < vmem_bytes("bool", 128, n)
    arcs = np.concatenate([powerlaw_graph(256, 1500, alpha=1.5, seed=4),
                           [[0, n - 1]]])
    at.clear_cache()
    res = at.autotune(arcs, n, "bool", batch=128, include_kernels=True,
                      top_k=1)
    kern = [c for c in res.candidates if c["config"].use_kernel]
    assert kern and all(f"VMEM at (128, {n})" in c["skipped"] for c in kern)
    assert not res.config.use_kernel
    tc = "tc(X,Y) <- arc(X,Y).\ntc(X,Y) <- tc(X,Z), arc(Z,Y)."
    # the service at 128 finds that search in the cache; at 8 it tunes anew
    for max_batch, reason in ((128, f"VMEM at (128, {n})"),
                              (8, "backend cpu")):
        svc = DatalogService(tc, db={"arc": arcs}, sparse=True, tune=True)
        front = AsyncDatalogService(svc, max_batch=max_batch)
        front.ask(("tc", (0, None)), timeout=120)
        front.close()
        cands = svc.explain()["kernels"]["tuning"]["tc"]["candidates"]
        kern = [c for c in cands if c["config"]["use_kernel"]]
        assert kern and all(reason in c["skipped"] for c in kern), max_batch


def test_autotune_without_single_width_candidate():
    """Slice candidates that leave out the single-width layout still tune:
    that layout is always measured as the gain's baseline."""
    from repro.data.graphs import powerlaw_graph
    from repro.kernels import autotune as at
    edges = powerlaw_graph(256, 1500, alpha=1.5, seed=4)
    res = at.autotune(edges, 256, "bool", include_kernels=False,
                      slice_candidates=((2, 1), (1, 1)), use_cache=False)
    base = [c for c in res.candidates if c["config"] == at.SINGLE_WIDTH]
    assert len(base) == 1 and base[0]["measured_s"] is not None
    assert res.gain > 0


def test_pallas_service_refuses_width_over_vmem():
    """``matmul='pallas'`` at a frontier width whose kernel the scoped VMEM
    cannot hold raises the typed error naming the width and the bytes —
    before Mosaic, and without falling back to the XLA path."""
    from repro.kernels.spmv import VmemBudgetError, check_vmem, vmem_bytes
    from repro.service import DatalogService
    tc = "tc(X,Y) <- arc(X,Y).\ntc(X,Y) <- tc(X,Z), arc(Z,Y)."
    n = 1 << 17  # 32 padded rows x 2^17 bool columns: 16 MiB of output
    edges = np.array([[0, 1], [1, 2], [2, n - 1]], np.int64)
    need = vmem_bytes("bool", 32, n)
    with pytest.raises(VmemBudgetError) as err:
        check_vmem("bool", 32, n)
    assert err.value.width == n and err.value.need == need
    assert str(n) in str(err.value) and str(need) in str(err.value)
    svc = DatalogService(tc, db={"arc": edges}, sparse=True,
                         matmul="pallas", bits=18)
    burst = [("tc", (s, None)) for s in range(20)]  # pads to 32 rows
    with pytest.raises(VmemBudgetError, match=str(need)):
        svc.ask_batch(burst)
    # narrow batches fit: the same service answers them through the kernel
    assert {tuple(r) for r in svc.ask("tc", (0, None))} == \
        {(0, 1), (0, 2), (0, n - 1)}


# ---------------------------------------------------------------------------
# additive (plus-times) and max-plus carriers (ROADMAP item 4)
# ---------------------------------------------------------------------------


def _rand_weighted_csr(n, p, kind, seed=0, acyclic=False):
    from repro.core.sparse import build_csr
    rng = np.random.default_rng(seed)
    mask = rng.random((n, n)) < p
    np.fill_diagonal(mask, False)
    if acyclic:
        mask = np.triu(mask, k=1)
    src, dst = np.nonzero(mask)
    edges = np.stack([src, dst, rng.integers(1, 5, len(src))],
                     axis=1).astype(np.int64)
    return build_csr(edges, n, kind), edges


@given(st.sampled_from([1, 3, 8]), st.sampled_from([64, 100]),
       st.sampled_from([0.02, 0.1]))
@settings(max_examples=6, deadline=None)
def test_csr_plustimes_spmv_vs_dense(b, n, p):
    """Segment-SUM over packed arcs == dense f32 matmul.  Integer-valued
    weights/frontiers keep every partial sum exact in f32, so the equality
    is bitwise regardless of reduction order (sentinel pads carry 0 and
    contribute nothing)."""
    csr, edges = _rand_weighted_csr(n, p, "plustimes", seed=n + b)
    w = np.zeros((n, n), np.float32)
    np.add.at(w, (edges[:, 0], edges[:, 1]), edges[:, 2].astype(np.float32))
    f = np.where(RNG.random((b, n)) < 0.3,
                 RNG.integers(1, 5, (b, n)), 0).astype(np.float32)
    want = jnp.matmul(jnp.asarray(f), jnp.asarray(w))
    got = ops.csr_plustimes(jnp.asarray(f), csr.src_idx, csr.col_idx,
                            csr.edge_val)
    assert jnp.array_equal(got, want)


@given(st.sampled_from([1, 3, 8]), st.sampled_from([64, 100]),
       st.sampled_from([0.02, 0.1]))
@settings(max_examples=6, deadline=None)
def test_csr_maxplus_spmv_vs_dense(b, n, p):
    """Segment-MAX over packed arcs == the dense max-plus product (the
    min-plus kernel reflected through negation; -inf sentinels never win)."""
    csr, edges = _rand_weighted_csr(n, p, "maxplus", seed=n + b)
    w = np.full((n, n), -np.inf, np.float32)
    np.maximum.at(w, (edges[:, 0], edges[:, 1]), edges[:, 2].astype(np.float32))
    f = np.asarray(-rand_dist(b, n, 0.3))  # finite entries > -inf
    want = -ref.minplus_ref(jnp.asarray(-f), jnp.asarray(-w))
    got = ops.csr_maxplus(jnp.asarray(f), csr.src_idx, csr.col_idx,
                          csr.edge_val)
    assert jnp.array_equal(got, want)


def test_csr_weighted_kernel_steps_match_jnp_segment_path():
    """``csr_frontier_step('plustimes'|'maxplus')`` (Pallas) agrees with the
    jnp sliced-ELL oracle steps in ``core.sparse`` — spine AND COO tail."""
    from repro.core import sparse
    csr, _ = _rand_weighted_csr(96, 0.05, "plustimes", seed=5, acyclic=True)
    csr = sparse.csr_append(csr, np.array([[0, 95, 2], [3, 95, 1]], np.int64))
    assert int(csr.tail_nnz) > 0
    f = np.where(RNG.random((4, 96)) < 0.3,
                 RNG.integers(1, 5, (4, 96)), 0).astype(np.float32)
    assert jnp.array_equal(ops.csr_frontier_step("plustimes")(jnp.asarray(f), csr),
                           sparse.csr_frontier_sum(jnp.asarray(f), csr))
    csr_m, _ = _rand_weighted_csr(96, 0.05, "maxplus", seed=6)
    fm = jnp.asarray(np.asarray(-rand_dist(4, 96, 0.3)))
    assert jnp.array_equal(ops.csr_frontier_step("maxplus")(fm, csr_m),
                           sparse.csr_frontier_max(fm, csr_m))


def test_plustimes_kernel_drives_counting_fixpoint():
    """The one-hot MXU plus-times step is a drop-in spmv for the accumulate-
    form CSR fixpoint and matches the dense counting closure exactly."""
    from repro.core import sparse
    from repro.core.seminaive import counts_batch_dense
    csr, edges = _rand_weighted_csr(80, 0.06, "plustimes", seed=9,
                                    acyclic=True)
    w = np.zeros((80, 80), np.float32)
    np.add.at(w, (edges[:, 0], edges[:, 1]), edges[:, 2].astype(np.float32))
    srcs = [0, 7, 40]
    got = sparse.counts_batch_csr(csr, srcs,
                                  spmv=ops.csr_frontier_step("plustimes"))
    want = counts_batch_dense(jnp.asarray(w), srcs)
    assert jnp.array_equal(got.table[:, :80], want.table[:, :80])
