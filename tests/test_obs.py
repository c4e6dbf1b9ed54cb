"""Observability layer tests: tracing, metrics, probes, cache thread-safety.

Covers the ``repro.obs`` package and its integration into the serving
stack:

* ``Tracer`` span mechanics + Chrome ``trace_event`` JSON field validation;
* span nesting across the admission pipeline — two racing submitter threads
  must yield disjoint, *well-formed* per-thread traces (any two spans on one
  tid are either disjoint or properly nested, never partially overlapping);
* ``MetricsRegistry`` under concurrent mutation: exact totals, exporter
  formats, collector absorption;
* probed fixpoint twins are bit-identical to the unprobed fixpoints and
  their per-iteration Δ-fact counts sum to the oracle's derived-fact total;
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
    KernelAttribution,
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


# -- probed fixpoint twins --------------------------------------------------

@pytest.mark.parametrize("sparse", [False, True],
                         ids=["dense", "csr"])
def test_probed_bit_identical_and_delta_oracle(sparse):
    edges = gnp(48, 0.08, seed=3)
    eng = Engine(TC, db={"arc": edges}, default_cap=4096)
    for src in (0, 5, 17):
        plain = eng.ask_dense("tc", (src, None), sparse=sparse)
        got, pr = eng.ask_dense("tc", (src, None), sparse=sparse, probe=True)
        assert np.array_equal(np.asarray(plain), np.asarray(got)), \
            "probed twin must be bit-identical"
        want = ref_reachable(edges, src)
        assert pr.final_facts == len(want)
        # per-iteration Δ-fact counts sum to the oracle's derived total
        assert pr.seed_facts + pr.total_delta == len(want)
        assert pr.repr == ("csr" if sparse else "dense")
        assert pr.iterations == len(pr.delta_facts) == len(pr.frontier_rows)
        d = pr.as_dict()
        assert d["repr"] == pr.repr and d["final_facts"] == len(want)


def test_probed_minplus_matches_oracle_distances():
    rng = np.random.default_rng(7)
    w = np.asarray([[a, b, int(rng.integers(1, 9))]
                    for a, b in gnp(24, 0.12, seed=11)], np.int64)
    eng = Engine(SP, db={"w": w}, default_cap=4096)
    plain = eng.ask_dense("sp", (0, None))
    got, pr = eng.ask_dense("sp", (0, None), probe=True)
    for a, b in zip(plain, got):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    rows, vals = got
    want = ref_distances(w, 0)
    assert {int(r[1]): int(v) for r, v in zip(rows, vals)} == want
    # min-plus Δ counts improvements; the final fact count still matches
    assert pr.final_facts == len(want)


def test_service_probe_mode_answers_and_explain():
    edges = gnp(40, 0.08, seed=5)
    queries = [f"tc({s}, X)" for s in (0, 3, 9, 12)]
    base = DatalogService(TC, db={"arc": edges}, default_cap=4096)
    svc = DatalogService(TC, db={"arc": edges}, default_cap=4096, probe=True)
    for a, b in zip(base.ask_batch(queries), svc.ask_batch(queries)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            "probe mode must not change answers"
    assert svc.last_probes, "probe mode should record fixpoint probes"
    rep = svc.explain()
    assert rep["probes"] and rep["probes"][-1]["iterations"] >= 1
    # batched probe Δ accounting: seed + ΣΔ == final, per probe record
    for p in svc.last_probes:
        assert p.seed_facts + p.total_delta == p.final_facts


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
    submits = [e for e in evs if e["name"] == "submit"]
    assert len(submits) == len(queries)
    assert len({e["tid"] for e in submits}) == 2  # two racing submitter tids
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


# -- roofline attribution ---------------------------------------------------

def test_kernel_attribution_report():
    from repro.roofline.report import V5E
    ka = KernelAttribution(hw=V5E)
    cost = dense_launch_cost(B=8, n=1024, itemsize=4, iters=10)
    assert cost["flops"] == 2 * 8 * 1024 * 1024 * 10
    ka.record("frontier_matmul:bool", seconds=0.01, iterations=10, **cost)
    ka.record("frontier_matmul:bool", seconds=0.01, iterations=10, **cost)
    ccost = csr_launch_cost(B=8, n_alloc=1024, e_alloc=4096, itemsize=4,
                            iters=5)
    assert ccost["flops"] == 2 * 8 * 4096 * 5
    ka.record("csr_spmv:bool", seconds=0.002, iterations=5, **ccost)
    rep = ka.report()
    mm = rep["frontier_matmul:bool"]
    assert mm["launches"] == 2 and mm["iterations"] == 20
    assert mm["achieved_flops_per_s"] == pytest.approx(
        2 * cost["flops"] / 0.02)
    assert mm["frac_peak_flops"] == pytest.approx(
        mm["achieved_flops_per_s"] / V5E.peak_flops)
    assert 0 < mm["frac_peak_flops"] and mm["dominant"] in ("compute",
                                                            "memory")
    assert rep["csr_spmv:bool"]["launches"] == 1
    ka.clear()
    assert ka.report() == {}
    # peaks come from the running device's kind: the CPU has none, so
    # nothing is reported as a fraction of a chip's peak
    cpu = KernelAttribution()
    cpu.record("csr_spmv:bool", seconds=0.002, iterations=5, **ccost)
    row = cpu.report()["csr_spmv:bool"]
    assert row["achieved_flops_per_s"] > 0
    assert row["frac_peak_flops"] is None and row["frac_peak_bw"] is None
    assert row["dominant"] is None


def test_service_kernel_attribution_in_explain():
    svc = DatalogService(TC, db={"arc": gnp(64, 0.06, seed=2)},
                         default_cap=4096)
    svc.ask_batch(["tc(0, X)", "tc(1, X)", "tc(2, X)"])
    kernels = svc.explain()["kernels"]
    assert kernels, "frontier launches should be attributed"
    for name, k in kernels.items():
        if name == "tuning":  # autotuner report, not a launch record
            continue
        assert name.split(":")[0] in ("frontier_matmul", "csr_spmv")
        assert k["launches"] >= 1 and k["seconds"] > 0
        # the tests run on the CPU, whose kind has no peaks: not measured
        assert k["dominant"] is None
        assert k["frac_peak_flops"] is None and k["frac_peak_bw"] is None


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
    """Regression: the probe/fixpoint fact counters asked for ``jnp.int64``,
    which silently realizes as int32 without ``jax_enable_x64`` — so the
    saturation guard was checking a bound the counter couldn't represent.
    The counters must carry ``GEN_DTYPE`` (the dtype that actually exists)
    end to end, and Δ accounting must balance exactly: seed + ΣΔ == final."""
    import jax.numpy as jnp

    from repro.core.seminaive import GEN_DTYPE, GEN_MAX
    from repro.obs.fixpoint_probe import fixpoint_dense_probed
    from repro.core.semiring import BOOL

    assert GEN_MAX == jnp.iinfo(GEN_DTYPE).max  # guard checks the real bound
    edges = gnp(32, 0.1, seed=2)
    adj = np.zeros((32, 32), bool)
    adj[edges[:, 0], edges[:, 1]] = True
    init = np.zeros((3, 32), bool)
    init[[0, 1, 2], [0, 5, 9]] = True
    res, pr = fixpoint_dense_probed(BOOL, jnp.asarray(adj), jnp.asarray(init))
    assert res.generated.dtype == GEN_DTYPE
    assert pr.seed_facts + pr.total_delta == pr.final_facts
    assert 0 <= pr.total_delta < int(GEN_MAX)


def test_probed_twins_reject_additive_carriers():
    """The probed twins replicate the masked vector form; the additive
    (+,×) carrier runs the accumulate form, so probing it must be a loud
    NotImplementedError — and probe-mode counting services answer
    correctly while recording no probes for the additive relation."""
    import jax.numpy as jnp

    from repro.core.semiring import PLUS_TIMES
    from repro.core.sparse import build_csr
    from repro.obs.fixpoint_probe import fixpoint_csr_probed, fixpoint_dense_probed

    edges = np.array([[0, 1, 1], [1, 2, 1], [0, 2, 1]], np.int64)
    w = np.zeros((8, 8), np.float32)
    w[edges[:, 0], edges[:, 1]] = 1.0
    with pytest.raises(NotImplementedError):
        fixpoint_dense_probed(PLUS_TIMES, jnp.asarray(w), jnp.asarray(w[:1]))
    with pytest.raises(NotImplementedError):
        fixpoint_csr_probed(build_csr(edges, 8, "plustimes"),
                            jnp.zeros((1, 8), jnp.float32))
    cpath = ("cpath(X,Z,sum<C>) <- d(X,Z,C).\n"
             "cpath(X,Z,sum<C>) <- cpath(X,Y,C1), d(Y,Z,C2), C = C1 * C2.")
    svc = DatalogService(cpath, db={"d": edges}, probe=True)
    rows, vals = svc.ask("cpath", (0, None, None))
    assert {(int(r[1]), int(v)) for r, v in zip(rows, vals)} == \
        {(1, 1), (2, 2)}
    assert not svc.last_probes, "additive batches must run unprobed"
