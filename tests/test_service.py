"""Query-serving subsystem: DatalogService, micro-batching, caches, appends.

Equivalence bar: every micro-batched / cached / resumed answer must equal the
corresponding independent ``Engine.ask()`` — across semirings (bool TC/sg,
min-plus shortest paths), across appends, and across cache states.
"""
import numpy as np
import pytest
from _hypothesis_stub import given, settings, st

from repro.core import engine as engine_mod
from repro.core.engine import Engine
from repro.core.planner import PlanError
from repro.service import DatalogService
from repro.service.batch import pad_batch_size
from repro.service.cache import CacheEntry, LRUCache

TC = """
tc(X,Y) <- arc(X,Y).
tc(X,Y) <- tc(X,Z), arc(Z,Y).
"""

SG = """
sg(X,Y) <- arc(P,X), arc(P,Y), X != Y.
sg(X,Y) <- arc(A,X), sg(A,B), arc(B,Y).
"""

SPATH = """
dpath(X,Z,min<D>) <- darc(X,Z,D).
dpath(X,Z,min<D>) <- dpath(X,Y,Dxy), darc(Y,Z,Dyz), D = Dxy + Dyz.
"""

EDGES = np.array([[0, 1], [1, 2], [2, 3], [3, 1], [4, 0], [5, 6], [2, 5]])


def rows_set(rows):
    return {tuple(map(int, r)) for r in rows}


def agg_set(res):
    rows, vals = res
    return {(*map(int, r), int(v)) for r, v in zip(rows, vals)}


# ---------------------------------------------------------------------------
# batched == N independent Engine.ask
# ---------------------------------------------------------------------------


def test_batch_tc_equals_sequential_ask():
    svc = DatalogService(TC, db={"arc": EDGES}, default_cap=2048)
    eng = Engine(TC, db={"arc": EDGES}, default_cap=2048)
    sources = [0, 1, 2, 4, 5]
    batched = svc.ask_batch([("tc", (s, None)) for s in sources])
    for s, rows in zip(sources, batched):
        assert rows_set(rows) == rows_set(eng.ask("tc", (s, None))), s
    # the whole batch ran as ONE dense fixpoint
    assert svc.stats.dense_fixpoints == 1
    assert svc.stats.batched_queries == len(sources)


def test_batch_sg_equals_sequential_ask():
    arc = np.array([[0, 2], [0, 3], [1, 4], [1, 5], [2, 6], [3, 7], [4, 8]])
    svc = DatalogService(SG, db={"arc": arc}, default_cap=4096)
    eng = Engine(SG, db={"arc": arc}, default_cap=4096)
    sources = [2, 3, 6]
    batched = svc.ask_batch([("sg", (s, None)) for s in sources])
    for s, rows in zip(sources, batched):
        assert rows_set(rows) == rows_set(eng.ask("sg", (s, None))), s
    # sg is not decomposable: served by ONE memoized tuple template
    assert svc.stats.plans_built == 1
    assert svc.stats.plan_hits == len(sources) - 1


def test_batch_spath_minplus_equals_sequential_ask():
    darc = np.array([[0, 1, 4], [0, 2, 1], [2, 1, 1], [1, 3, 2], [3, 0, 7],
                     [2, 3, 9], [5, 6, 2]])
    svc = DatalogService(SPATH, db={"darc": darc}, default_cap=2048)
    eng = Engine(SPATH, db={"darc": darc}, default_cap=2048)
    sources = [0, 2, 5]
    batched = svc.ask_batch([("dpath", (s, None, None)) for s in sources])
    for s, res in zip(sources, batched):
        assert agg_set(res) == agg_set(eng.ask("dpath", (s, None, None))), s
    assert svc.stats.dense_fixpoints == 1


def test_mixed_batch_order_and_forms():
    svc = DatalogService(TC, db={"arc": EDGES}, default_cap=2048)
    eng = Engine(TC, db={"arc": EDGES}, default_cap=2048)
    res = svc.ask_batch(["tc(1, X)", ("tc", (None, 5)), ("arc", (2, None)),
                         "tc(1, X)"])
    assert rows_set(res[0]) == rows_set(eng.ask("tc", (1, None)))
    assert rows_set(res[1]) == rows_set(eng.ask("tc", (None, 5), verify=True))
    assert rows_set(res[2]) == {(2, 3), (2, 5)}
    assert rows_set(res[3]) == rows_set(res[0])


def test_tuple_template_filters_demanded_but_unqueried_rows():
    """The magic-restricted model may contain facts for *demanded* sources
    beyond the queried one (sg demands its ancestors' generations); both the
    service and Engine.ask must restrict to the query constants."""
    arc = np.array([[0, 2], [0, 3], [1, 4], [1, 5], [2, 6], [3, 7], [4, 8]])
    svc = DatalogService(SG, db={"arc": arc}, default_cap=4096)
    eng = Engine(SG, db={"arc": arc}, default_cap=4096).run()
    full = rows_set(eng.query("sg"))
    assert rows_set(svc.ask("sg", (6, None))) == {t for t in full if t[0] == 6}
    assert rows_set(eng.ask("sg", (6, None))) == {t for t in full if t[0] == 6}


def test_aggregate_cascade_demand_fallback():
    friend = np.array([[1, 0], [2, 0], [1, 2], [2, 1], [3, 1], [3, 2], [4, 3],
                       [4, 1], [5, 4], [5, 3]])
    organizer = np.array([[0], [2]])
    prog = """
    attend(X) <- organizer(X).
    attend(X) <- cntfriends(X,N), N >= 2.
    cntfriends(Y, count<X>) <- attend(X), friend(Y,X).
    """
    svc = DatalogService(prog, db={"friend": friend, "organizer": organizer},
                         default_cap=2048)
    assert rows_set(svc.ask("attend", (1,))) == {(1,)}
    assert rows_set(svc.ask("attend", (5,))) == {(5,)}
    assert len(svc.ask("attend", (9,))) == 0
    # constant-free model evaluated once, post-filtered per query
    assert svc.stats.tuple_runs == 3
    assert svc.stats.plans_built == 1


# ---------------------------------------------------------------------------
# property test: random graphs, batched == sequential (bool + min-plus)
# ---------------------------------------------------------------------------

N_EDGES = 12  # fixed size keeps padded shapes stable across examples

edges_strategy = st.lists(
    st.tuples(st.integers(0, 11), st.integers(0, 11)),
    min_size=N_EDGES, max_size=N_EDGES)

weighted_strategy = st.lists(
    st.tuples(st.integers(0, 9), st.integers(0, 9), st.integers(1, 8)),
    min_size=N_EDGES, max_size=N_EDGES)


@given(edges_strategy)
@settings(max_examples=5, deadline=None)
def test_property_batch_tc_and_sg(edge_list):
    edges = np.asarray(edge_list, np.int64)
    for prog, pred in ((TC, "tc"), (SG, "sg")):
        svc = DatalogService(prog, db={"arc": edges}, default_cap=2048)
        eng = Engine(prog, db={"arc": edges}, default_cap=2048)
        sources = [0, 3, 7]
        batched = svc.ask_batch([(pred, (s, None)) for s in sources])
        for s, rows in zip(sources, batched):
            assert rows_set(rows) == rows_set(eng.ask(pred, (s, None))), (pred, s)


@given(weighted_strategy)
@settings(max_examples=5, deadline=None)
def test_property_batch_spath_minplus(edge_list):
    darc = np.asarray(edge_list, np.int64)
    svc = DatalogService(SPATH, db={"darc": darc}, default_cap=2048)
    eng = Engine(SPATH, db={"darc": darc}, default_cap=2048)
    sources = [0, 5]
    batched = svc.ask_batch([("dpath", (s, None, None)) for s in sources])
    for s, res in zip(sources, batched):
        assert agg_set(res) == agg_set(eng.ask("dpath", (s, None, None))), s


# ---------------------------------------------------------------------------
# plan/trace caching: the Nth same-shape query never re-traces
# ---------------------------------------------------------------------------


def test_engine_ask_skips_retracing_on_same_shapes():
    """Satellite: Engine.ask's jitted fixpoints are cached on the structural
    plan key, so queries differing only in constants share the compile."""
    engine_mod.clear_runner_cache()  # deterministic cold start
    eng = Engine(TC, db={"arc": EDGES}, default_cap=2048)
    t0 = engine_mod.fixpoint_trace_count()
    eng.ask("tc", (1, None))
    traced_first = engine_mod.fixpoint_trace_count() - t0
    t1 = engine_mod.fixpoint_trace_count()
    eng.ask("tc", (2, None))
    eng.ask("tc", (4, None))
    assert traced_first >= 1  # the cold query did compile something
    assert engine_mod.fixpoint_trace_count() == t1  # warm queries: zero traces


def test_service_warm_batches_skip_retracing():
    """Warm tuple-path queries reuse the template's compiled fixpoints even
    when the materialized magic set varies in size — intermediate-strata
    shapes quantize to power-of-two buckets (seminaive.quantize_rows)."""
    svc = DatalogService(SG, db={"arc": EDGES}, default_cap=2048)
    svc.ask("sg", (0, None))  # cold: builds template + compiles
    t0 = engine_mod.fixpoint_trace_count()
    svc.ask("sg", (1, None))  # bigger demanded set than the cold query's
    svc.ask("sg", (3, None))
    assert engine_mod.fixpoint_trace_count() == t0
    assert svc.stats.plans_built == 1 and svc.stats.plan_hits == 2


# ---------------------------------------------------------------------------
# result cache
# ---------------------------------------------------------------------------


def test_result_cache_hits_and_eviction():
    svc = DatalogService(TC, db={"arc": EDGES}, default_cap=2048,
                         result_cache=2)
    svc.ask("tc", (0, None))
    svc.ask("tc", (0, None))
    assert svc.cache.hits == 1
    svc.ask("tc", (1, None))
    svc.ask("tc", (2, None))  # capacity 2: evicts (tc, 0, None)
    assert svc.cache.evictions >= 1
    svc.ask("tc", (0, None))  # miss again after eviction
    assert svc.cache.hits == 1


def test_result_cache_disabled():
    svc = DatalogService(TC, db={"arc": EDGES}, default_cap=2048,
                         result_cache=0)
    svc.ask("tc", (0, None))
    svc.ask("tc", (0, None))
    assert svc.cache.hits == 0 and len(svc.cache) == 0


def test_lru_cache_unit():
    c = LRUCache(2)
    e = lambda p: CacheEntry("tuple", p, None, 0)
    c.put("a", e("a")), c.put("b", e("b"))
    assert c.get("a") is not None  # bumps a
    c.put("c", e("c"))  # evicts b
    assert c.get("b") is None and c.get("a") is not None
    assert c.drop_where(lambda k, ent: ent.pred == "a") == 1
    assert len(c) == 1


# ---------------------------------------------------------------------------
# incremental appends
# ---------------------------------------------------------------------------


def test_append_resumes_dense_and_matches_fresh_engine():
    svc = DatalogService(TC, db={"arc": EDGES}, default_cap=2048)
    sources = [0, 4, 5]
    svc.ask_batch([("tc", (s, None)) for s in sources])
    svc.append("arc", [[6, 7], [3, 5]])
    appended = np.concatenate([EDGES, [[6, 7], [3, 5]]])
    eng = Engine(TC, db={"arc": appended}, default_cap=2048)
    assert svc.stats.resumed_rows == len(sources)
    hits0 = svc.cache.hits
    for s in sources:
        assert rows_set(svc.ask("tc", (s, None))) == \
            rows_set(eng.ask("tc", (s, None))), s
    # resumed entries serve straight from cache — no recompute
    assert svc.cache.hits == hits0 + len(sources)


@pytest.mark.parametrize("sparse", [True, False])
def test_append_resume_runs_in_launches_of_the_widest_pad(sparse):
    """More cached closures than the widest batch pad resume in several
    launches of at most that width (a wider fixpoint was never compiled
    and may not fit the device), with the same answers."""
    edges = np.stack([np.arange(40), (np.arange(40) * 7 + 3) % 40], axis=1)
    svc = DatalogService(TC, db={"arc": edges}, default_cap=2048,
                         batch_pads=(1, 8), sparse=sparse)
    sources = list(range(20))
    svc.ask_batch([("tc", (s, None)) for s in sources])
    before = svc.stats.dense_fixpoints
    svc.append("arc", [[3, 39], [39, 0]])
    assert svc.stats.resumed_rows == len(sources)
    assert svc.stats.dense_fixpoints - before == 3  # 8 + 8 + 4 rows
    eng = Engine(TC, db={"arc": np.concatenate([edges, [[3, 39], [39, 0]]])},
                 default_cap=2048)
    for s in sources:
        assert rows_set(svc.ask("tc", (s, None))) == \
            rows_set(eng.ask("tc", (s, None))), s


def test_append_grows_domain_past_allocation():
    svc = DatalogService(TC, db={"arc": EDGES}, default_cap=2048)
    svc.ask("tc", (0, None))
    assert svc.explain()["relations"]["tc"]["n_alloc"] == 128
    svc.append("arc", [[3, 200]])
    assert svc.explain()["relations"]["tc"]["n_alloc"] == 256
    eng = Engine(TC, db={"arc": np.concatenate([EDGES, [[3, 200]]])},
                 default_cap=2048)
    assert rows_set(svc.ask("tc", (0, None))) == \
        rows_set(eng.ask("tc", (0, None)))


def test_append_invalidates_tuple_results():
    arc = np.array([[0, 2], [0, 3], [2, 6], [3, 7]])
    svc = DatalogService(SG, db={"arc": arc}, default_cap=2048)
    assert rows_set(svc.ask("sg", (6, None))) == {(6, 7)}
    svc.append("arc", [[0, 4], [4, 8], [2, 9]])
    appended = np.concatenate([arc, [[0, 4], [4, 8], [2, 9]]])
    eng = Engine(SG, db={"arc": appended}, default_cap=2048)
    assert rows_set(svc.ask("sg", (6, None))) == \
        rows_set(eng.ask("sg", (6, None)))
    assert svc.cache.hits == 0  # tuple entry was dropped, not reused


def test_append_minplus_improves_distances():
    darc = np.array([[0, 1, 9], [1, 2, 1], [0, 3, 1]])
    svc = DatalogService(SPATH, db={"darc": darc}, default_cap=2048)
    assert agg_set(svc.ask("dpath", (0, None, None))) == \
        {(0, 1, 9), (0, 2, 10), (0, 3, 1)}
    svc.append("darc", [[3, 1, 1]])  # shortcut: 0->3->1 = 2
    assert agg_set(svc.ask("dpath", (0, None, None))) == \
        {(0, 1, 2), (0, 2, 3), (0, 3, 1)}


def test_append_validation():
    svc = DatalogService(TC, db={"arc": EDGES}, default_cap=2048)
    with pytest.raises(ValueError):
        svc.append("tc", [[1, 2]])  # IDB: not appendable
    with pytest.raises(ValueError):
        svc.append("arc", [[1, 2, 3]])  # arity mismatch
    with pytest.raises(ValueError):
        svc.append("arc", [[1, 1 << 40]])  # outside the packed domain


# ---------------------------------------------------------------------------
# batching plumbing
# ---------------------------------------------------------------------------


def test_pad_batch_size_levels():
    pads = (1, 8, 32, 128)
    assert pad_batch_size(1, pads) == 1
    assert pad_batch_size(2, pads) == 8
    assert pad_batch_size(9, pads) == 32
    assert pad_batch_size(128, pads) == 128
    assert pad_batch_size(129, pads) == 256


def test_duplicate_sources_coalesce():
    svc = DatalogService(TC, db={"arc": EDGES}, default_cap=2048)
    res = svc.ask_batch([("tc", (1, None))] * 4)
    assert svc.stats.batched_queries == 1  # deduped inside the batch
    for rows in res[1:]:
        assert rows_set(rows) == rows_set(res[0])


def test_duplicate_tuple_queries_coalesce():
    arc = np.array([[0, 2], [0, 3], [2, 6], [3, 7]])
    svc = DatalogService(SG, db={"arc": arc}, default_cap=2048)
    res = svc.ask_batch([("sg", (2, None))] * 3)
    assert svc.stats.tuple_runs == 1  # one template fixpoint for the burst
    for rows in res:
        assert rows_set(rows) == {(2, 3)}


def test_out_of_domain_source_is_empty():
    svc = DatalogService(TC, db={"arc": EDGES}, default_cap=2048)
    assert len(svc.ask("tc", (1000, None))) == 0


def test_repeated_variable_queries():
    """tc(X, X) constrains like a constant: distinct cache key from
    tc(X, Y), equality-filtered result, on every path (service + Engine)."""
    arc = np.array([[0, 1], [1, 2], [2, 0], [3, 3], [4, 5]])
    svc = DatalogService(TC, db={"arc": arc}, default_cap=2048)
    eng = Engine(TC, db={"arc": arc}, default_cap=2048)
    all_rows = rows_set(svc.ask("tc(X, Y)"))
    diag = rows_set(svc.ask("tc(X, X)"))  # must NOT hit the tc(X, Y) entry
    assert diag == {(0, 0), (1, 1), (2, 2), (3, 3)}
    assert diag == {t for t in all_rows if t[0] == t[1]}
    assert rows_set(eng.ask("tc(X, X)", verify=True)) == diag
    # EDB selection path
    assert rows_set(svc.ask("arc(X, X)")) == {(3, 3)}
    assert rows_set(eng.ask("arc(X, X)")) == {(3, 3)}
    # dense lowering refuses a repeated-variable tail (it cannot enforce the
    # equality); the query routes through the tuple path and filters there
    from repro.core.ir import Var
    darc = np.array([[0, 1, 1], [1, 1, 2]])
    svp = DatalogService(SPATH, db={"darc": darc}, default_cap=2048)
    ep = Engine(SPATH, db={"darc": darc}, default_cap=2048)
    assert agg_set(svp.ask("dpath(0, X, X)")) == {(0, 1, 1)}
    assert agg_set(ep.ask("dpath(0, X, X)", verify=True)) == {(0, 1, 1)}
    with pytest.raises(PlanError):
        ep.ask_dense("dpath", (0, Var("X"), Var("X")))


def test_batched_vector_fixpoint_runs_to_domain_depth():
    """Regression: a (B, n) batched vector fixpoint must iterate to the
    DOMAIN's depth, not 4*B+8 — a long chain with a small batch exposed it."""
    import jax.numpy as jnp
    from repro.core.seminaive import (distances_batch_dense, fixpoint_dense,
                                      reachable_batch_dense)
    from repro.core.semiring import BOOL
    n = 60
    adj = jnp.zeros((n, n), bool).at[jnp.arange(n - 1), jnp.arange(1, n)].set(True)
    res = fixpoint_dense(BOOL, adj, adj[jnp.asarray([0])], form="vector")
    assert int(res.table[0].sum()) == n - 1  # every chain vertex reached
    # the batch front-ends agree (cached-jit path)
    resb = reachable_batch_dense(adj, [0, 30])
    assert int(resb.table[0].sum()) == n - 1
    assert int(resb.table[1].sum()) == n - 31
    w = jnp.where(adj, 1.0, jnp.inf).astype(jnp.float32)
    resd = distances_batch_dense(w, [0])
    assert float(resd.table[0][n - 1]) == n - 1  # chain distance = hop count


def test_distributed_resume_frontier_matches_recompute():
    """Mesh-path append-resume: resuming the Fig.-4 sharded frontier fixpoint
    from prev ⊕ seed equals recomputing the closure over the appended arcs."""
    import jax
    import jax.numpy as jnp
    from repro.core.distributed import (resume_frontier_decomposable,
                                        tc_frontier_decomposable)
    mesh = jax.make_mesh((1,), ("data",))
    n = 8
    adj = np.zeros((n, n), bool)
    for a, b in [(0, 1), (1, 2), (4, 5)]:
        adj[a, b] = True
    frontier = jnp.asarray(adj[np.array([0, 4])])
    prev, _ = tc_frontier_decomposable(mesh, jnp.asarray(adj), frontier)
    adj2 = adj.copy()
    adj2[2, 4] = True  # the append
    seed = jnp.asarray(adj2[np.array([0, 4])])
    resumed, _ = resume_frontier_decomposable(mesh, jnp.asarray(adj2), prev, seed)
    scratch, _ = tc_frontier_decomposable(mesh, jnp.asarray(adj2), seed)
    assert bool(jnp.array_equal(resumed, scratch))


def test_wrong_arity_query_raises():
    svc = DatalogService(TC, db={"arc": EDGES}, default_cap=2048)
    with pytest.raises(PlanError):
        svc.ask("tc", (1, None, None))  # tc is 2-ary
    with pytest.raises(PlanError):
        svc.ask("arc", (1,))  # arc is 2-ary


# ---------------------------------------------------------------------------
# qid-batched tuple fixpoints: B same-shape queries, ONE PSN evaluation
# ---------------------------------------------------------------------------


def test_tuple_batch_one_fixpoint_matches_sequential():
    """B same-shape sg queries coalesce into one qid-tagged fixpoint whose
    per-seed answers equal B sequential Engine.ask() calls exactly."""
    arc = np.array([[0, 2], [0, 3], [1, 4], [1, 5], [2, 6], [3, 7], [4, 8]])
    svc = DatalogService(SG, db={"arc": arc}, default_cap=4096)
    eng = Engine(SG, db={"arc": arc}, default_cap=4096)
    sources = [2, 3, 6, 4]
    batched = svc.ask_batch([("sg", (s, None)) for s in sources])
    for s, rows in zip(sources, batched):
        assert rows_set(rows) == rows_set(eng.ask("sg", (s, None))), s
    assert svc.stats.tuple_fixpoints == 1
    assert svc.stats.tuple_batched_queries == len(sources)
    # per-qid answers were cached individually: singletons now hit
    h0 = svc.cache.hits
    svc.ask("sg", (3, None))
    assert svc.cache.hits == h0 + 1


def test_tuple_batch_fully_bound_boolean_queries():
    """tc(a, b) boolean queries adorn as 'bb': the batch coalesces on the
    two-column seed schema and each qid answer is the 0/1-row restriction."""
    svc = DatalogService(SG, db={"arc": np.array(
        [[0, 2], [0, 3], [2, 6], [3, 7]])}, default_cap=4096)
    eng = Engine(SG, db={"arc": svc.db["arc"]}, default_cap=4096)
    pairs = [(2, 3), (6, 7), (2, 6), (3, 2)]
    batched = svc.ask_batch([("sg", p) for p in pairs])
    for p, rows in zip(pairs, batched):
        assert rows_set(rows) == rows_set(eng.ask("sg", p)), p
    assert rows_set(batched[0]) == {(2, 3)} and len(batched[2]) == 0
    assert svc.stats.tuple_fixpoints == 1
    assert svc.stats.tuple_batched_queries == len(pairs)


def test_tuple_batch_repeated_variable_queries():
    """sg(c, X) and sg(c, c') share a shape only when adornments match;
    sg(X, X) ('ff') never enters a seeded batch.  All answers must equal the
    sequential path, including the repeated-variable equality filter."""
    arc = np.array([[0, 1], [1, 2], [2, 0], [0, 2], [3, 3]])
    svc = DatalogService(TC, db={"arc": arc}, default_cap=2048)
    eng = Engine(TC, db={"arc": arc}, default_cap=2048)
    queries = ["tc(0, 2)", "tc(1, 1)", "tc(X, X)", "tc(2, 2)"]
    res = svc.ask_batch(queries)
    for q, rows in zip(queries, res):
        assert rows_set(rows) == rows_set(eng.ask(q)), q
    # the three 'bb' queries batched; tc(X, X) went through the ff model
    assert svc.stats.tuple_batched_queries == 3


def test_mixed_adornment_batches_do_not_coalesce():
    """sg(c, X) ('bf') and sg(c, c') ('bb') demand different seed schemas —
    they must evaluate as separate fixpoints, never one.  Shapes that do not
    admit per-seed attribution at all ('fb' adorns an all-free occurrence)
    fall back to sequential evaluation inside the same batch."""
    arc = np.array([[0, 2], [0, 3], [1, 4], [1, 5], [2, 6], [3, 7], [4, 8]])
    svc = DatalogService(SG, db={"arc": arc}, default_cap=4096)
    eng = Engine(SG, db={"arc": arc}, default_cap=4096)
    queries = [("sg", (2, None)), ("sg", (2, 3)), ("sg", (3, None)),
               ("sg", (6, 7)), ("sg", (None, 7)), ("sg", (None, 6))]
    res = svc.ask_batch(queries)
    for q, rows in zip(queries, res):
        assert rows_set(rows) == rows_set(eng.ask(*q)), q
    # 'bf' and 'bb' batch separately; the 'fb' pair runs sequentially
    assert svc.stats.tuple_fixpoints == 2
    assert svc.stats.tuple_batched_queries == 4
    names = sorted(p_a.split("+")[0] for p_a in svc.explain()["templates"])
    assert names == ["sg/bb", "sg/bf", "sg/fb"]


def test_tuple_batch_agg_shapes():
    """min-agg tuple batches: point-distance queries adorn 'bbf' (the value
    position is always free; a value *constant* rides the same shape as a
    residual filter) and split per qid with values.  The dense router must
    not claim them — their tails are not all-free."""
    darc = np.array([[0, 1, 4], [0, 2, 1], [2, 1, 1], [1, 3, 2], [3, 0, 7],
                     [2, 3, 9], [5, 6, 2]])
    svc = DatalogService(SPATH, db={"darc": darc}, default_cap=2048)
    eng = Engine(SPATH, db={"darc": darc}, default_cap=2048)
    queries = [("dpath", (0, 3, None)), ("dpath", (0, 1, None)),
               ("dpath", (2, 3, None)), ("dpath", (0, 3, 4))]
    res = svc.ask_batch(queries)
    for q, r in zip(queries, res):
        assert agg_set(r) == agg_set(eng.ask(*q)), q
    assert agg_set(res[0]) == {(0, 3, 4)} and agg_set(res[3]) == {(0, 3, 4)}
    assert svc.stats.tuple_fixpoints == 1
    assert svc.stats.tuple_batched_queries == 4


def test_tuple_batch_warm_shapes_skip_retracing():
    """CI satellite: a warm tuple batch whose padded shapes (seed bucket +
    magic-set buckets) repeat reuses the compiled batched fixpoint —
    fixpoint_trace_count() must not move.  (Different sources can cross a
    quantize_rows bucket when the union demand set grows; same sources on a
    cleared result cache hold every shape fixed.)"""
    arc = np.array([[0, 2], [0, 3], [1, 4], [1, 5], [2, 6], [3, 7], [4, 8]])
    svc = DatalogService(SG, db={"arc": arc}, default_cap=4096)
    batch = [("sg", (s, None)) for s in [2, 3, 6]]
    svc.ask_batch(batch)  # cold: compiles the qid fixpoint
    svc.cache.clear()
    t0 = engine_mod.fixpoint_trace_count()
    svc.ask_batch(batch)  # warm: same shapes, zero traces
    assert engine_mod.fixpoint_trace_count() == t0
    assert svc.stats.tuple_fixpoints == 2


def test_engine_ask_batch_matches_ask():
    """Engine-level ask_batch: same-shape goals share one fixpoint; EDB
    selections, mixed shapes and all-free goals fall back transparently."""
    eng = Engine(TC, db={"arc": EDGES}, default_cap=2048)
    queries = ["tc(0, 3)", "tc(4, 2)", ("arc", (2, None)), "tc(1, X)",
               ("tc", (None, 5)), "tc(9, 9)"]
    res = eng.ask_batch(queries)
    for q, rows in zip(queries, res):
        want = eng.ask(q) if not (isinstance(q, tuple) and q[0] == "arc") \
            else eng.ask(*q)
        assert rows_set(rows) == rows_set(want), q


def test_engine_multi_goal_program_batches():
    """Parser -> IR -> planner wiring: a program with several same-shape
    '?-' goals plans ONE qid-batched fixpoint; batch_results() splits."""
    eng = Engine(TC + "?- tc(1, X).\n?- tc(4, X).\n?- tc(5, X).",
                 db={"arc": EDGES}, default_cap=2048).run()
    ref = Engine(TC, db={"arc": EDGES}, default_cap=2048)
    for s, rows in zip([1, 4, 5], eng.batch_results()):
        assert rows_set(rows) == rows_set(ref.ask("tc", (s, None))), s
    with pytest.raises(ValueError):  # mixed shapes refuse a single plan
        Engine(TC + "?- tc(1, X).\n?- tc(X, 5).", db={"arc": EDGES})


# ---------------------------------------------------------------------------
# incremental: tuple snapshot resume + eviction-aware policy
# ---------------------------------------------------------------------------


def test_tuple_batch_append_resumes_snapshot():
    """A batched tuple template snapshots its fixpoint state; a monotone
    append re-enters from that state (same seeds) and refreshes the per-qid
    cache entries instead of invalidating them."""
    arc = np.array([[0, 2], [0, 3], [1, 4], [1, 5], [2, 6], [3, 7], [4, 8]])
    svc = DatalogService(SG, db={"arc": arc}, default_cap=4096)
    sources = [2, 3, 6]
    svc.ask_batch([("sg", (s, None)) for s in sources])
    svc.append("arc", [[6, 9], [7, 10]])
    assert svc.stats.resumed_tuple_rows == len(sources)
    appended = np.concatenate([arc, [[6, 9], [7, 10]]])
    eng = Engine(SG, db={"arc": appended}, default_cap=4096)
    h0 = svc.cache.hits
    for s in sources:
        assert rows_set(svc.ask("sg", (s, None))) == \
            rows_set(eng.ask("sg", (s, None))), s
    assert svc.cache.hits == h0 + len(sources)  # served from refreshed cache


def test_eviction_aware_append_resume_drops_cold_tail():
    """Satellite regression: with resume_min_hits=1, only entries that
    served a query since their last compute resume on append; the cold LRU
    tail is EVICTED (dropped_cold counts it), not recomputed."""
    svc = DatalogService(TC, db={"arc": EDGES}, default_cap=2048,
                         resume_min_hits=1)
    sources = [0, 4, 5]
    svc.ask_batch([("tc", (s, None)) for s in sources])
    svc.ask("tc", (0, None))  # source 0 is hot (one serve since compute)
    fx0 = svc.stats.dense_fixpoints
    svc.append("arc", [[6, 7], [3, 5]])
    assert svc.stats.resumed_rows == 1  # only the hot entry resumed
    assert svc.stats.dropped_cold == 2  # cold tail evicted, not maintained
    key_cold = ("tc", 4, "~1")
    assert key_cold not in svc.cache
    # cold source recomputes (fresh fixpoint) and is still correct
    appended = np.concatenate([EDGES, [[6, 7], [3, 5]]])
    eng = Engine(TC, db={"arc": appended}, default_cap=2048)
    assert rows_set(svc.ask("tc", (4, None))) == \
        rows_set(eng.ask("tc", (4, None)))
    assert svc.stats.dense_fixpoints > fx0 + 1  # resume + the recompute
    # the hot entry serves straight from the refreshed cache
    h0 = svc.cache.hits
    assert rows_set(svc.ask("tc", (0, None))) == \
        rows_set(eng.ask("tc", (0, None)))
    assert svc.cache.hits == h0 + 1


def test_warm_start_guard_rejects_unsound_programs():
    """Engine.run(warm=) must refuse programs where warm rows corrupt the
    model: additive aggregates double-bill, negation keeps refuted facts.
    min/max and plain sets re-converge exactly (the service's resume gate)."""
    deg = "deg(X, count<Y>) <- e(X, Y).\n"
    e = np.array([[0, 1], [0, 2], [1, 2]])
    eng = Engine(deg, db={"e": e}, default_cap=256).run()
    warm = dict(eng.materialized)
    eng2 = Engine(deg, db={"e": np.concatenate([e, [[1, 3]]])},
                  default_cap=256)
    with pytest.raises(PlanError):
        eng2.run(warm=warm)
    neg = "alone(X) <- v(X), ~e(X, X).\n"
    engn = Engine(neg, db={"e": e, "v": np.array([[0], [1]])},
                  default_cap=256).run()
    with pytest.raises(PlanError):
        Engine(neg, db={"e": e, "v": np.array([[0], [1]])},
               default_cap=256).run(warm=dict(engn.materialized))


def test_append_to_unrelated_relation_revalidates_snapshot_entries():
    """Appending to an EDB a batched template never reads must not re-run
    its fixpoint NOR drop its cached answers — they revalidate in place."""
    prog = SG + "\nother(X,Y) <- extra(X,Y).\n"
    arc = np.array([[0, 2], [0, 3], [2, 6], [3, 7]])
    extra = np.array([[1, 1]])
    svc = DatalogService(prog, db={"arc": arc, "extra": extra},
                         default_cap=4096)
    svc.ask_batch([("sg", (2, None)), ("sg", (3, None))])
    runs0 = svc.stats.tuple_runs
    svc.append("extra", [[5, 5]])
    assert svc.stats.resumed_tuple_rows == 0  # nothing re-ran
    h0 = svc.cache.hits
    assert rows_set(svc.ask("sg", (2, None))) == {(2, 3)}
    assert svc.cache.hits == h0 + 1 and svc.stats.tuple_runs == runs0


def test_tuple_snapshot_resumes_hot_subset_only():
    """Under resume_min_hits, only the HOT positions of a batched snapshot
    resume: cold seeds leave the re-entered fixpoint and the next snapshot;
    their entries evict and a later ask recomputes them correctly."""
    arc = np.array([[0, 2], [0, 3], [1, 4], [1, 5], [2, 6], [3, 7], [4, 8]])
    svc = DatalogService(SG, db={"arc": arc}, default_cap=4096,
                         resume_min_hits=1)
    sources = [2, 3, 6]
    svc.ask_batch([("sg", (s, None)) for s in sources])
    svc.ask("sg", (3, None))  # only source 3 is hot
    svc.append("arc", [[6, 9], [7, 10]])
    assert svc.stats.resumed_tuple_rows == 1
    assert svc.stats.dropped_cold == 2
    appended = np.concatenate([arc, [[6, 9], [7, 10]]])
    eng = Engine(SG, db={"arc": appended}, default_cap=4096)
    h0 = svc.cache.hits
    assert rows_set(svc.ask("sg", (3, None))) == \
        rows_set(eng.ask("sg", (3, None)))  # hot: refreshed cache entry
    assert svc.cache.hits == h0 + 1
    for s in (2, 6):  # cold: evicted, recomputed fresh, still correct
        assert rows_set(svc.ask("sg", (s, None))) == \
            rows_set(eng.ask("sg", (s, None))), s
    # a second append resumes only the surviving snapshot position
    svc.ask("sg", (3, None))
    svc.append("arc", [[8, 11]])
    assert svc.stats.resumed_tuple_rows == 2


def test_tuple_snapshot_respects_hit_policy():
    """Under resume_min_hits, a batched tuple snapshot none of whose entries
    were hit is dropped on append (no maintenance fixpoint for it)."""
    arc = np.array([[0, 2], [0, 3], [2, 6], [3, 7]])
    svc = DatalogService(SG, db={"arc": arc}, default_cap=4096,
                         resume_min_hits=1)
    svc.ask_batch([("sg", (2, None)), ("sg", (3, None))])
    svc.append("arc", [[0, 4], [4, 8]])
    assert svc.stats.resumed_tuple_rows == 0
    assert svc.stats.dropped_cold >= 2
    # correctness after the drop: recomputed answers match a fresh engine
    appended = np.concatenate([arc, [[0, 4], [4, 8]]])
    eng = Engine(SG, db={"arc": appended}, default_cap=4096)
    assert rows_set(svc.ask("sg", (2, None))) == \
        rows_set(eng.ask("sg", (2, None)))


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_serve_cli_smoke(capsys):
    from repro.service.serve import main
    rc = main(["--synthetic", "paths:4:2", "--batch",
               "--query", "tc(0, X)", "--query", "tc(3, X)",
               "--append", "arc:2,3", "--query", "tc(0, X)", "--stats"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "tc(0, X)  [2 rows]" in out
    assert "appended 1 rows to arc (epoch 1)" in out
    # the appended 2->3 links path 0 onto path 1: closure 0->{1,2,3,4,5}
    assert "tc(0, X)  [5 rows]" in out
    assert '"appends": 1' in out


# ---------------------------------------------------------------------------
# carrier routing regressions: max-plus / plus-times on the fast path
# ---------------------------------------------------------------------------

LPATH = """
lpath(X,Z,max<D>) <- darc(X,Z,D).
lpath(X,Z,max<D>) <- lpath(X,Y,D1), darc(Y,Z,D2), D = D1 + D2.
"""

CPATH = """
cpath(X,Z,sum<C>) <- darc(X,Z,C).
cpath(X,Z,sum<C>) <- cpath(X,Y,C1), darc(Y,Z,C2), C = C1 * C2.
"""

#: a diamond where longest and shortest routes genuinely differ:
#: 0->3 direct (1), 0->1->3 (2+2=4), 0->1->2->3 (2+1+5=8)
DIAMOND = np.array([[0, 3, 1], [0, 1, 2], [1, 3, 2],
                    [1, 2, 1], [2, 3, 5]], np.int64)


@pytest.mark.parametrize("force", [False, True], ids=["dense", "csr"])
def test_maxplus_program_routes_max_carrier(force):
    """Regression for the carrier-misrouting bug: the dense serving layer
    hardwired ``BOOL if kind == 'bool' else MIN_PLUS``, so a ``max<D>``
    program was silently served on the min-plus carrier — longest-path
    queries returned SHORTEST paths.  The typed carrier table routes by
    lowering kind; on the diamond the two answers differ (8 vs 1)."""
    svc = DatalogService(LPATH, db={"darc": DIAMOND}, sparse=force)
    got = agg_set(svc.ask("lpath", (0, None, None)))
    assert got == {(0, 1, 2), (0, 2, 3), (0, 3, 8)}
    assert (0, 3, 8) in got and (0, 3, 1) not in got, \
        "served the min-plus carrier for a max<> program"
    assert svc.explain()["relations"]["lpath"]["semiring"] == "max_plus"
    # the tuple engine (slow path) agrees
    assert got == agg_set(Engine(LPATH, db={"darc": DIAMOND})
                          .ask("lpath", (0, None, None)))


@pytest.mark.parametrize("force", [False, True], ids=["dense", "csr"])
def test_counting_program_serves_exact_counts(force):
    """sum<> programs route to the additive (+,×) carrier and serve exact
    integer path counts on both representations (diamond: 3 routes 0→3)."""
    ones = DIAMOND.copy()
    ones[:, 2] = 1  # unit weights: sums count distinct paths
    svc = DatalogService(CPATH, db={"darc": ones}, sparse=force)
    got = agg_set(svc.ask("cpath", (0, None, None)))
    assert got == {(0, 1, 1), (0, 2, 1), (0, 3, 3)}
    assert svc.explain()["relations"]["cpath"]["semiring"] == "plus_times"
    assert got == agg_set(Engine(CPATH, db={"darc": ones})
                          .ask("cpath", (0, None, None)))


def test_duplicate_edb_rows_are_set_semantics():
    """Regression: EDB relations are SETS of facts.  A duplicated row used
    to be enumerated twice by the tuple engine's additive aggregates (and
    double-scattered into the dense carrier) — invisible for bool/min/max,
    which are duplicate-insensitive, but it doubled counts.  Loading or
    appending an exact duplicate must change nothing."""
    ones = DIAMOND.copy()
    ones[:, 2] = 1
    dup = np.concatenate([ones, ones[:2], ones[:1]], axis=0)
    want = {(0, 1, 1), (0, 2, 1), (0, 3, 3)}
    assert agg_set(Engine(CPATH, db={"darc": dup})
                   .ask("cpath", (0, None, None))) == want
    for force in (False, True):
        svc = DatalogService(CPATH, db={"darc": dup}, sparse=force)
        assert agg_set(svc.ask("cpath", (0, None, None))) == want
        svc.append("darc", ones[2:4])  # duplicates again, post-load
        assert agg_set(svc.ask("cpath", (0, None, None))) == want


def test_unknown_lowering_kind_raises_typed_error():
    """carrier_for / edge_arity reject unknown kinds with CarrierError
    instead of silently defaulting a carrier (how the misrouting started)."""
    from repro.core.semiring import CarrierError, carrier_for, edge_arity
    with pytest.raises(CarrierError):
        carrier_for("geometric-mean")
    with pytest.raises(CarrierError):
        edge_arity("geometric-mean")
    assert edge_arity("bool") == 2
    assert {edge_arity(k) for k in ("minplus", "maxplus", "plustimes")} == {3}
