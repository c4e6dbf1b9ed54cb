"""Observability layer tests: tracing, metrics, fixpoint counters, cache
thread-safety.

Covers the ``repro.obs`` package and its integration into the serving
stack:

* ``Tracer`` span mechanics + Chrome ``trace_event`` JSON field validation;
* span nesting across the admission pipeline — two racing submitter threads
  must yield disjoint, *well-formed* per-thread traces (any two spans on one
  tid are either disjoint or properly nested, never partially overlapping);
* ``MetricsRegistry`` under concurrent mutation: exact totals, exporter
  formats, collector absorption;
* the fixpoint loops' own counters: Δ entries sum to the oracle's
  derived-fact total (seed + Δ == final), active rows match known depths,
  and the service reports their running totals through ``explain()``;
* the ``LRUCache.hits``/``CacheEntry.hits`` thread-safety regression: the
  bumps used to be bare ``+=`` racing between submitter threads and the
  dispatcher — exact counts under a thread hammer prove the lock.
"""
import json
import threading
import time

import numpy as np
import pytest
from _reference import ref_distances, ref_reachable

from repro.core.engine import Engine
from repro.obs import (
    DEFAULT_BUCKETS,
    MetricsRegistry,
    NULL_METRICS,
    NULL_TRACER,
    Tracer,
    csr_launch_cost,
    dense_launch_cost,
)
from repro.service import AsyncDatalogService, DatalogService
from repro.service.cache import CacheEntry, LRUCache

TC = "tc(X,Y) <- arc(X,Y).\ntc(X,Y) <- tc(X,Z), arc(Z,Y)."
SP = ("sp(X,Y,min<D>) <- w(X,Y,D).\n"
      "sp(X,Y,min<D>) <- sp(X,Z,D1), w(Z,Y,D2), D = D1 + D2.")


def ring(n: int) -> np.ndarray:
    return np.asarray([[i, (i + 1) % n] for i in range(n)], np.int64)


def gnp(n: int, p: float, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    a = rng.random((n, n)) < p
    np.fill_diagonal(a, False)
    r, c = np.nonzero(a)
    return np.stack([r, c], axis=1).astype(np.int64)


# -- tracer unit ------------------------------------------------------------

REQUIRED_X = ("name", "cat", "ph", "ts", "dur", "pid", "tid")


def well_formed(spans) -> bool:
    """Any two spans on one tid are disjoint or properly nested."""
    for i, a in enumerate(spans):
        for b in spans[i + 1:]:
            if a["tid"] != b["tid"] or not Tracer.overlaps(a, b):
                continue
            a0, a1 = a["ts"], a["ts"] + a["dur"]
            b0, b1 = b["ts"], b["ts"] + b["dur"]
            if not ((a0 <= b0 and b1 <= a1) or (b0 <= a0 and a1 <= b1)):
                return False
    return True


def test_tracer_span_fields_and_nesting():
    tr = Tracer()
    with tr.span("outer", cat="service", k=1):
        time.sleep(0.001)
        with tr.span("inner", cat="device"):
            time.sleep(0.001)
    tr.instant("mark", cat="service", n=3)
    evs = tr.events()
    xs = [e for e in evs if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["inner", "outer"]  # children end first
    for e in xs:
        for field in REQUIRED_X:
            assert field in e, f"missing {field} in {e}"
        assert e["ts"] >= 0 and e["dur"] >= 0
    inner, outer = xs
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert outer["args"] == {"k": 1}
    (inst,) = [e for e in evs if e["ph"] == "i"]
    assert inst["name"] == "mark" and inst["args"] == {"n": 3}
    assert well_formed(xs)


def test_tracer_annotate_idempotent_end_and_filters():
    tr = Tracer()
    sp = tr.span("s", cat="c")
    sp.annotate(batch=4)
    sp.end()
    sp.end()  # idempotent: no duplicate event
    with sp:   # with-block after explicit end() is also a no-op
        pass
    assert len(tr.spans("s")) == 1
    assert tr.spans("s")[0]["args"] == {"batch": 4}
    assert tr.spans("nope") == []
    tr.clear()
    assert tr.events() == []


def test_tracer_chrome_export_round_trip(tmp_path):
    tr = Tracer()
    with tr.span("a"):
        pass
    path = tmp_path / "trace.json"
    tr.export_chrome(str(path))
    doc = json.loads(path.read_text())
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    for e in doc["traceEvents"]:
        assert e["ph"] in ("X", "i")
        for field in ("name", "cat", "ph", "ts", "pid", "tid"):
            assert field in e
        if e["ph"] == "X":
            assert "dur" in e


def test_tracer_concurrent_threads_exact_and_well_formed():
    tr = Tracer()
    threads, per = 6, 40
    gate = threading.Barrier(threads)  # all alive at once -> distinct tids

    def work():
        gate.wait()
        for i in range(per):
            with tr.span("step", i=i):
                with tr.span("sub"):
                    pass

    ts = [threading.Thread(target=work) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    xs = tr.spans()
    assert len(xs) == threads * per * 2
    assert len({e["tid"] for e in xs}) == threads
    assert well_formed(xs)


def test_null_tracer_is_free_and_silent(tmp_path):
    assert NULL_TRACER.enabled is False
    s1 = NULL_TRACER.span("a", x=1)
    s2 = NULL_TRACER.span("b")
    assert s1 is s2  # shared no-op span: no per-call allocation
    with s1:
        s1.annotate(y=2)
    NULL_TRACER.instant("i")
    assert NULL_TRACER.events() == [] and NULL_TRACER.spans() == []
    path = tmp_path / "null.json"
    NULL_TRACER.export_chrome(str(path))
    assert json.loads(path.read_text())["traceEvents"] == []


# -- metrics unit -----------------------------------------------------------

def test_metrics_counter_gauge_histogram_basics():
    m = MetricsRegistry()
    c = m.counter("datalog_things_total", "things")
    c.inc()
    c.inc(2, labels={"kind": "a"})
    assert c.value() == 1 and c.value({"kind": "a"}) == 2
    g = m.gauge("datalog_depth")
    g.set(5)
    g.dec()
    assert g.value() == 4
    h = m.histogram("datalog_lat_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count() == 3
    p = h.percentiles((50, 99))
    assert p["p50"] == 0.5 and p["p99"] == 5.0
    # same name returns the same object; kind conflicts raise
    assert m.counter("datalog_things_total") is c
    with pytest.raises(TypeError):
        m.gauge("datalog_things_total")
    with pytest.raises(TypeError):
        m.histogram("datalog_depth")


def test_metrics_registry_concurrency_exact_totals():
    m = MetricsRegistry()
    c = m.counter("datalog_hammer_total")
    h = m.histogram("datalog_hammer_seconds")
    threads, per = 8, 2000

    def work(tid):
        for i in range(per):
            c.inc()
            c.inc(labels={"t": str(tid % 2)})
            h.observe(1e-3 * (i % 7))

    ts = [threading.Thread(target=work, args=(i,)) for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value() == threads * per
    assert (c.value({"t": "0"}) + c.value({"t": "1"})) == threads * per
    assert h.count() == threads * per


def test_metrics_prometheus_and_json_formats():
    m = MetricsRegistry()
    m.counter("datalog_q_total", "queries").inc(3, labels={"engine": "dense"})
    h = m.histogram("datalog_s_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = m.to_prometheus()
    assert "# TYPE datalog_q_total counter" in text
    assert 'datalog_q_total{engine="dense"} 3' in text
    assert "# TYPE datalog_s_seconds histogram" in text
    # cumulative buckets: 0.1 -> 1, 1.0 -> 2, +Inf -> 3 == _count
    assert 'datalog_s_seconds_bucket{le="0.1"} 1' in text
    assert 'datalog_s_seconds_bucket{le="1.0"} 2' in text
    assert 'datalog_s_seconds_bucket{le="+Inf"} 3' in text
    assert "datalog_s_seconds_count 3" in text
    assert "datalog_s_seconds_sum 5.55" in text
    doc = m.to_json()
    assert doc["datalog_q_total"]["kind"] == "counter"
    assert doc["datalog_q_total"]["series"]['{engine="dense"}'] == 3
    assert doc["datalog_s_seconds"]["series"]["_"]["count"] == 3


def test_metrics_collector_absorption_and_export(tmp_path):
    m = MetricsRegistry()
    external = {"done": 0}
    m.register_collector(
        lambda reg: reg.counter("datalog_done_total").set(external["done"]))
    external["done"] = 7
    assert "datalog_done_total 7" in m.to_prometheus()
    external["done"] = 9  # collectors re-run on every export
    prom = tmp_path / "m.prom"
    m.export(str(prom))
    assert "datalog_done_total 9" in prom.read_text()
    jpath = tmp_path / "m.json"
    m.export(str(jpath))
    assert json.loads(jpath.read_text())["datalog_done_total"]["series"]["_"] == 9


def test_null_metrics_accepts_everything():
    n = NULL_METRICS
    assert n.enabled is False
    n.counter("x").inc()
    n.gauge("y").set(3)
    n.histogram("z").observe(1.0)
    assert n.counter("x").value() == 0.0
    assert np.isnan(n.histogram("z").percentiles((50,))["p50"])
    n.register_collector(lambda reg: 1 / 0)  # never runs
    n.collect()
    assert n.to_prometheus() == "" and n.to_json() == {}


# -- LRU cache thread-safety regression (the bare += races) -----------------

def test_lru_cache_hit_counts_exact_under_threads():
    cache = LRUCache(64)
    cache.put(("tc", 0, None),
              CacheEntry("dense", "tc", np.zeros((1, 2), np.int64), epoch=0))
    threads, per = 8, 3000

    def work(tid):
        for i in range(per):
            ent = cache.get(("tc", 0, None))       # hit: bumps both counters
            assert ent is not None
            cache.get(("miss", tid, i))            # miss
            if i % 100 == 0:                       # churn the OrderedDict too
                cache.put(("k", tid, i),
                          CacheEntry("tuple", "tc", None, epoch=0))

    ts = [threading.Thread(target=work, args=(i,)) for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # the regression: bare `+=` under free-threading lost updates here
    assert cache.hits == threads * per
    assert cache.peek(("tc", 0, None)).hits == threads * per
    assert cache.misses == threads * per


# -- fixpoint counters -------------------------------------------------------

def facts(table) -> int:
    """Entries of a closure table that are not the ⊕-zero."""
    t = np.asarray(table)
    return int(np.count_nonzero(t if t.dtype == bool else np.isfinite(t)))


def fixpoint_on(sparse: bool, edges: np.ndarray, n: int, srcs, kind="bool"):
    """The batched frontier fixpoint of the serving path, dense or CSR."""
    import jax.numpy as jnp

    from repro.core import seminaive as sn
    from repro.core import sparse as sp
    if sparse:
        csr = sp.build_csr(edges, n, kind)
        run = sp.reachable_batch_csr if kind == "bool" \
            else sp.distances_batch_csr
        return sp.rows_from_sources(csr, srcs), run(csr, srcs)
    if kind == "bool":
        adj = np.zeros((n, n), bool)
        adj[edges[:, 0], edges[:, 1]] = True
        return adj[srcs], sn.reachable_batch_dense(jnp.asarray(adj), srcs)
    w = np.full((n, n), np.inf, np.float32)
    np.minimum.at(w, (edges[:, 0], edges[:, 1]), edges[:, 2].astype(np.float32))
    return w[srcs], sn.distances_batch_dense(jnp.asarray(w), srcs)


@pytest.mark.parametrize("sparse", [False, True],
                         ids=["dense", "csr"])
def test_delta_facts_oracle(sparse):
    edges = gnp(48, 0.08, seed=3)
    srcs = [0, 5, 17]
    seed, res = fixpoint_on(sparse, edges, 48, srcs)
    table = np.asarray(res.table)
    want = [ref_reachable(edges, s) for s in srcs]
    for j, s in enumerate(srcs):
        assert set(np.nonzero(table[j])[0].tolist()) == set(want[j])
    # the loop's Δ entries sum to the oracle's derived-fact total
    assert facts(seed) + int(res.delta_facts) == facts(table) \
        == sum(len(w) for w in want)
    # every row is active entering the first step, and some row entering
    # each later one; no more than all rows in any
    assert int(res.iterations) <= int(res.active_rows) \
        <= len(srcs) * int(res.iterations)


@pytest.mark.parametrize("sparse", [False, True],
                         ids=["dense", "csr"])
def test_minplus_delta_facts_matches_oracle_distances(sparse):
    rng = np.random.default_rng(7)
    w = np.asarray([[a, b, int(rng.integers(1, 9))]
                    for a, b in gnp(24, 0.12, seed=11)], np.int64)
    seed, res = fixpoint_on(sparse, w, 24, [0], kind="minplus")
    row = np.asarray(res.table)[0]
    want = ref_distances(w, 0)
    got = {int(v): int(row[v]) for v in np.nonzero(np.isfinite(row))[0]}
    assert got == want
    # min-plus Δ counts improvements too: at least the facts found, and the
    # final fact count still matches the oracle
    assert facts(res.table) == len(want)
    assert int(res.delta_facts) >= facts(res.table) - facts(seed)
    # one row: active entering every step, the last one included
    assert int(res.active_rows) == int(res.iterations)


@pytest.mark.parametrize("sparse", [False, True],
                         ids=["dense", "csr"])
def test_active_rows_on_a_chain_at_a_padded_width(sparse):
    """On the chain 0 -> 1 -> ... -> n-1, source s's row is active entering
    the n-1-s steps that add a vertex and the one that finds none; a pad
    row only entering the first.  The loop runs until the deepest row
    settles."""
    from repro.core.semiring import BOOL
    from repro.service.batch import run_frontier_batch, run_frontier_batch_csr

    n = 24
    edges = np.asarray([[i, i + 1] for i in range(n - 1)], np.int64)
    srcs = [0, 10, 22]  # depths 22, 12 and 0; pad rows: 5 at width 8
    if sparse:
        from repro.core.sparse import build_csr
        res = run_frontier_batch_csr(build_csr(edges, n), srcs, (8,))
    else:
        import jax.numpy as jnp
        adj = np.zeros((n, n), bool)
        adj[edges[:, 0], edges[:, 1]] = True
        res = run_frontier_batch(BOOL, jnp.asarray(adj), srcs, (8,))
    assert np.asarray(res.table).shape == (8, n)
    depth = [n - 2 - s for s in srcs]  # vertices each row adds
    assert int(res.iterations) == max(depth) + 1
    assert int(res.active_rows) == sum(d + 1 for d in depth) + (8 - len(srcs))
    assert int(res.delta_facts) == sum(depth)


def test_service_fixpoint_totals_in_explain():
    edges = gnp(40, 0.08, seed=5)
    srcs = (0, 3, 9, 12)
    svc = DatalogService(TC, db={"arc": edges}, default_cap=4096,
                         batch_pads=(8,))
    answers = svc.ask_batch([f"tc({s}, X)" for s in srcs])
    for s, a in zip(srcs, answers):
        assert {int(r[1]) for r in np.asarray(a)} == set(ref_reachable(edges, s))
    svc.ask_batch([f"tc({s}, X)" for s in srcs])  # cache hits: no launch
    fx = svc.explain()["relations"]["tc"]["fixpoint"]
    assert fx["launches"] == 1 and fx["rows"] == 4 and fx["pad_rows"] == 8
    assert fx["iterations"] >= 1
    assert fx["row_iterations"] == 8 * fx["iterations"]
    assert fx["iterations"] <= fx["active_rows"] <= fx["row_iterations"]
    # batched Δ accounting: seed rows (the sources' out-arcs) + ΣΔ == final
    seed = sum(int((edges[:, 0] == s).sum()) for s in srcs)
    assert seed + fx["delta_facts"] == sum(len(a) for a in answers)


# -- service tracing integration -------------------------------------------

def test_service_trace_spans_nested(tmp_path):
    svc = DatalogService(TC, db={"arc": ring(32)}, default_cap=4096,
                         tracer=True)
    svc.ask_batch(["tc(0, X)", "tc(5, X)"])
    svc.append("arc", np.asarray([[0, 16]], np.int64))
    names = {e["name"] for e in svc.tracer.spans()}
    assert {"launch_batch", "fixpoint", "finalize_batch", "device_sync",
            "cache_fill", "append"} <= names
    xs = svc.tracer.spans()
    assert well_formed(xs)

    def inside(inner, outer):
        return (inner["ts"] >= outer["ts"] and
                inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])

    (lb,) = svc.tracer.spans("launch_batch")
    (fp,) = svc.tracer.spans("fixpoint")
    (fb,) = svc.tracer.spans("finalize_batch")
    (cf,) = svc.tracer.spans("cache_fill")
    assert inside(fp, lb) and inside(cf, fb)
    assert fp["cat"] == "device" and lb["cat"] == "service"
    path = tmp_path / "svc_trace.json"
    svc.tracer.export_chrome(str(path))
    doc = json.loads(path.read_text())
    assert len(doc["traceEvents"]) == len(svc.tracer.events())


def test_admission_racing_submitters_disjoint_well_formed_traces():
    svc = DatalogService(TC, db={"arc": ring(48)}, default_cap=4096,
                         tracer=True)
    front = AsyncDatalogService(svc, max_wait_ms=1.0, max_batch=4)
    queries = [f"tc({s}, X)" for s in range(8)]
    futs: list = [None] * len(queries)
    gate = threading.Barrier(2)  # both submitters alive -> distinct tids

    def submit(lo, hi):
        gate.wait()
        for i in range(lo, hi):
            futs[i] = front.submit(queries[i])

    half = len(queries) // 2
    workers = [threading.Thread(target=submit, args=(0, half)),
               threading.Thread(target=submit, args=(half, len(queries)))]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    for f in futs:
        assert f.result(timeout=120) is not None
    front.close()

    evs = svc.tracer.events()
    xs = [e for e in evs if e["ph"] == "X"]
    # every per-thread lane is independently well-formed: the racing
    # submitters, the dispatcher and the finalizer never corrupt each other
    assert well_formed(xs)
    # each query's queue wait is on exactly one flush span, and each flush
    # span names the launch it covers
    flushes = [e for e in xs if e["name"] == "flush"]
    waits = [w for e in flushes for w in e["args"]["queue_wait_ms"]]
    assert len(waits) == len(queries) and all(w >= 0 for w in waits)
    launched = {e["args"]["flush"] for e in xs if e["name"] == "launch_batch"}
    assert {e["args"]["flush"] for e in flushes} == launched
    assert not [e for e in evs if e["ph"] == "i"]  # no per-query instants
    coalesce = [e for e in xs if e["name"] == "coalesce"]
    assert coalesce and all("batch" in e.get("args", {}) for e in coalesce)
    assert sum(e["args"]["batch"] for e in coalesce) == len(queries)
    # the pipeline stages all ran under tracing
    names = {e["name"] for e in xs}
    assert {"launch_batch", "finalize_batch"} <= names


# -- metrics through the serving stack --------------------------------------

def test_service_metrics_unified_schema():
    svc = DatalogService(TC, db={"arc": ring(32)}, default_cap=4096)
    svc.ask_batch(["tc(0, X)", "tc(3, X)"])
    svc.ask_batch(["tc(0, X)"])  # cache hit
    svc.append("arc", np.asarray([[1, 20]], np.int64))
    text = svc.metrics.to_prometheus()
    for needle in ("datalog_fixpoints_total", "datalog_cache_hits_total",
                   "datalog_batched_queries_total", "datalog_appends_total",
                   "datalog_epoch", "datalog_batch_size",
                   "datalog_fixpoint_traces_total"):
        assert needle in text, f"{needle} missing from unified schema"
    m = svc.metrics
    assert m.counter("datalog_cache_hits_total").value() >= 1
    assert m.gauge("datalog_epoch").value() == 1
    assert m.histogram("datalog_batch_size").count() == 2  # two launches


def test_admission_metrics_and_explain_canonical_schema():
    svc = DatalogService(TC, db={"arc": ring(32)}, default_cap=4096)
    front = AsyncDatalogService(svc, max_wait_ms=1.0, max_batch=4)
    futs = [front.submit(f"tc({s}, X)") for s in (0, 1, 2, 3)]
    for f in futs:
        f.result(timeout=120)
    rep = front.explain()
    front.close()
    adm = rep["admission"]
    # canonical nested schema only — the deprecated flat aliases are gone
    assert adm["counters"]["submitted"] == 4
    assert adm["queue"]["depth"] == 0 and "limit" in adm["queue"]
    assert "max_wait_ms" in adm["window"]
    assert "submitted" not in adm and "queue_depth" not in adm
    assert "mean_flush" not in adm and "max_batch" not in adm
    assert "service" in rep and "stats" not in rep
    assert "relations" in rep and "dense" not in rep
    text = svc.metrics.to_prometheus()
    assert 'datalog_admission_total{event="submitted"} 4' in text
    assert "datalog_queue_wait_seconds_count 4" in text


# -- launch cost model ------------------------------------------------------

def test_launch_cost_formulas():
    from repro.obs.roofline_attr import achieved_fractions
    from repro.roofline.report import V5E
    cost = dense_launch_cost(B=8, n=1024, itemsize=4, iters=10)
    assert cost["flops"] == 2 * 8 * 1024 * 1024 * 10
    assert cost["bytes"] == 4 * (1024 * 1024 + 2 * 8 * 1024) * 10
    ccost = csr_launch_cost(B=8, n_alloc=1024, e_alloc=4096, itemsize=4,
                            iters=5)
    assert ccost["flops"] == 2 * 8 * 4096 * 5
    assert ccost["bytes"] == (4096 * 12 + 4 * 2 * 8 * 1024
                              + 4 * 8 * 4096) * 5
    frac = achieved_fractions(ccost, 0.002, V5E)
    assert frac["frac_peak_flops"] == pytest.approx(
        ccost["flops"] / 0.002 / V5E.peak_flops)
    assert frac["frac_peak_bw"] == pytest.approx(
        ccost["bytes"] / 0.002 / V5E.hbm_bw)
    # without a chip's peaks nothing is reported as a fraction of one
    assert achieved_fractions(ccost, 0.002, None) == {
        "frac_peak_flops": None, "frac_peak_bw": None}


def test_service_explain_kernels_has_no_launch_records():
    svc = DatalogService(TC, db={"arc": gnp(64, 0.06, seed=2)},
                         default_cap=4096)
    svc.ask_batch(["tc(0, X)", "tc(1, X)", "tc(2, X)"])
    rep = svc.explain()
    # only the autotuner's entries live there, and this service tunes none
    assert rep["kernels"] == {}
    assert rep["relations"]["tc"]["fixpoint"]["launches"] == 1


# -- trace-counter race + generated-fact counter dtype regressions ----------

def test_trace_count_thread_hammer_exact():
    """Regression: ``bump_trace_count`` was a bare ``+=`` on a module global;
    with traces firing from the admission front-end's dispatcher/finalizer/
    submitter threads concurrently, updates were lost and ci.sh's warm-batch
    stability assertions (exact counts) flaked.  Exact totals under a thread
    hammer prove the lock."""
    from repro.core import seminaive
    threads, per = 16, 2000
    t0 = seminaive.trace_count()
    gate = threading.Barrier(threads)

    def work():
        gate.wait()
        for _ in range(per):
            seminaive.bump_trace_count()

    ts = [threading.Thread(target=work) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert seminaive.trace_count() - t0 == threads * per


def test_generated_counter_uses_realized_dtype():
    """Regression: the fixpoint fact counters asked for ``jnp.int64``,
    which silently realizes as int32 without ``jax_enable_x64`` — so a
    saturation check would test a bound the counter couldn't represent.
    The counters must carry ``GEN_DTYPE`` (the dtype that actually exists)
    end to end through the cached fixpoint, and Δ accounting must balance
    exactly: seed + ΣΔ == final."""
    import jax.numpy as jnp

    from repro.core.semiring import BOOL
    from repro.core.seminaive import GEN_DTYPE, GEN_MAX, fixpoint_dense_cached

    assert GEN_MAX == jnp.iinfo(GEN_DTYPE).max  # guard checks the real bound
    edges = gnp(32, 0.1, seed=2)
    adj = np.zeros((32, 32), bool)
    adj[edges[:, 0], edges[:, 1]] = True
    init = np.zeros((3, 32), bool)
    init[[0, 1, 2], [0, 5, 9]] = True
    res = fixpoint_dense_cached(BOOL, jnp.asarray(adj), jnp.asarray(init),
                                form="vector")
    assert res.generated.dtype == GEN_DTYPE
    assert res.delta_facts.dtype == GEN_DTYPE
    assert res.active_rows.dtype == jnp.int32
    assert facts(init) + int(res.delta_facts) == facts(res.table)
    assert 0 <= int(res.delta_facts) < int(GEN_MAX)


def accumulate_oracle(w: np.ndarray, srcs) -> tuple:
    """(iterations, rows with a non-zero Δ entering each step, non-zero
    entries of each new Δ), summed, by NumPy: Δ <- Δ·W until Δ drains."""
    delta = w[list(srcs)].astype(np.float64)
    it = active = entries = 0
    while delta.any():
        active += int(delta.any(axis=1).sum())
        delta = delta @ w
        entries += int(np.count_nonzero(delta))
        it += 1
    return it, active, entries


def test_additive_counters():
    """The accumulate form (plus-times) counts the rows whose Δ is non-zero
    entering each step and the entries of each new Δ — which are the
    generated facts — on the dense and CSR forms alike, and the service
    sums them per relation."""
    import jax.numpy as jnp

    from repro.core.seminaive import counts_batch_dense
    from repro.core.sparse import build_csr, counts_batch_csr

    edges = np.array([[0, 1, 1], [1, 2, 1], [0, 2, 1], [2, 3, 2],
                      [1, 3, 1]], np.int64)
    w = np.zeros((8, 8), np.float32)
    w[edges[:, 0], edges[:, 1]] = edges[:, 2]
    srcs = [0, 1, 5]
    want = accumulate_oracle(w, srcs)
    for res in (counts_batch_dense(jnp.asarray(w), srcs),
                counts_batch_csr(build_csr(edges, 8, "plustimes"), srcs)):
        got = (int(res.iterations), int(res.active_rows),
               int(res.delta_facts))
        assert got == want
        assert int(res.generated) == int(res.delta_facts)
    cpath = ("cpath(X,Z,sum<C>) <- d(X,Z,C).\n"
             "cpath(X,Z,sum<C>) <- cpath(X,Y,C1), d(Y,Z,C2), C = C1 * C2.")
    svc = DatalogService(cpath, db={"d": edges})
    rows, vals = svc.ask("cpath", (0, None, None))
    assert {(int(r[1]), int(v)) for r, v in zip(rows, vals)} == \
        {(1, 1), (2, 2), (3, 5)}
    fx = svc.explain()["relations"]["cpath"]["fixpoint"]
    it, active, entries = accumulate_oracle(w, [0])
    assert (fx["launches"], fx["iterations"]) == (1, it)
    # the pad rows of the launch are all-zero: never live
    assert (fx["active_rows"], fx["delta_facts"]) == (active, entries)


def test_named_scopes_in_compiled_fixpoint():
    """The seed build, the segment step and the convergence test carry
    stable names into the compiled program's op metadata, so a device
    trace can group kernel time by scope."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.core import sparse as sp

    csr = sp.build_csr(np.asarray([[i, i + 1] for i in range(15)]), 16)
    init = jax.ShapeDtypeStruct((8, 16), jnp.bool_)
    ops = re.findall(r'op_name="([^"]*)"',
                     sp.lower_fixpoint_csr(csr, init).compile().as_text())
    assert any("/while/body/csr_segment_step/" in o for o in ops)
    assert any("/while/body/fixpoint_converge/" in o for o in ops)
    seed = sp._rows_from_sources_jit.lower(csr, jnp.zeros(8, jnp.int32))
    assert any("/csr_seed/" in o for o in re.findall(
        r'op_name="([^"]*)"', seed.compile().as_text()))


def test_tracing_off_builds_no_flush_record(monkeypatch):
    """With the no-op tracer the admission flush opens only the shared
    no-op span and annotates nothing, so no per-query wait list is built,
    no flush is numbered and no lock is taken for the in-flight count;
    the queue-wait histogram still sees every query."""
    from repro.obs import trace

    class Recorder:
        """The shared no-op span, recording what it was handed."""

        def __init__(self):
            self.annotated = []

        def annotate(self, **kv):
            self.annotated.append(kv)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

        def end(self):
            pass

    rec, opened = Recorder(), []

    def span(name, cat="service", **args):
        opened.append((name, args))
        return rec

    made = []
    real_init = trace.Span.__init__

    def counting_init(self, *a, **kw):
        made.append(a[0] if a else None)
        real_init(self, *a, **kw)

    monkeypatch.setattr(NULL_TRACER, "span", span)
    monkeypatch.setattr(trace.Span, "__init__", counting_init)
    svc = DatalogService(TC, db={"arc": ring(24)}, default_cap=4096)
    assert svc.tracer is NULL_TRACER
    front = AsyncDatalogService(svc, max_wait_ms=1.0, max_batch=4)
    for f in [front.submit(f"tc({s}, X)") for s in range(6)]:
        f.result(timeout=120)
    front.close()
    assert not made, "no span object may be created with tracing off"
    # the coalescing window's batch size is the only annotation
    assert rec.annotated and all(kv.keys() == {"batch"} for kv in rec.annotated)
    flushes = [args for name, args in opened if name == "flush"]
    assert flushes and all(args == {} for args in flushes)
    assert all(args.get("flush") is None and args.get("ahead") is None
               for name, args in opened if name == "launch_batch")
    assert svc._flush_seq == 0 and svc._unsynced == 0
    assert svc.metrics.histogram("datalog_queue_wait_seconds").count() == 6


def test_flush_numbering_under_racing_threads():
    """The in-flight count is shared by the dispatcher (launch) and the
    finalizer (sync): under many submitters and a short switch interval
    every flush gets its own number and the count returns to zero."""
    import sys

    svc = DatalogService(TC, db={"arc": ring(40)}, default_cap=4096,
                         tracer=True, batch_pads=(4,))
    front = AsyncDatalogService(svc, max_wait_ms=0.5, max_batch=4,
                                inflight=2)
    futs, lock = [], threading.Lock()

    def submit(k):
        for s in range(k, 40, 8):
            f = front.submit(f"tc({s}, X)")
            with lock:
                futs.append(f)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=submit, args=(k,)) for k in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in ts)
        for f in futs:
            f.result(timeout=120)
        front.close()
    finally:
        sys.setswitchinterval(old)
    launches = svc.tracer.spans("launch_batch")
    seqs = [e["args"]["flush"] for e in launches]
    assert sorted(seqs) == list(range(1, len(launches) + 1))
    assert svc._unsynced == 0 and svc._flush_seq == len(launches)
    assert front.stats.flushes == len(launches)
