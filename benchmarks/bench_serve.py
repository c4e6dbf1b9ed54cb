"""Serving throughput: micro-batched fixpoints + caches vs per-query ask().

Workload: single-source TC queries against a >= 10k-edge random digraph
(the paper's Gn-p family at serving-friendly density).  Three regimes:

  * ``sequential``  — one ``Engine.ask()`` per query: the PR-1 interface;
    re-plans per query, solo tuple fixpoint (compiles amortize through the
    engine's runner cache after the first query).
  * ``service``     — ``DatalogService.ask_batch`` at B = 1 / 32 / 256:
    *cold* (first contact: compile + plan), *steady* (compile-warm, result
    cache cold — the honest serving number), and *warm* (result-cache hits).
  * ``append``      — appending edges to a warm service (resume cached
    closures from the delta frontier) vs recomputing those closures from
    scratch on an equally compile-warm service.
  * ``tuple_batch`` — B same-shape queries on a NON-decomposable predicate
    (same-generation): the qid-tagged magic rewrite evaluates the union of
    B demands in ONE tuple-path PSN fixpoint and splits answers per seed,
    vs B sequential ``Engine.ask()`` calls.

  * ``sparse``      — ``--sparse``: the CSR-packed frontier engine vs the
    dense matrix on a sparse Gn-p workload (|E| ≪ n²): same batched serving
    path, representation forced either way (``DatalogService(sparse=)``).

  * ``counting``    — ``--counting``: the additive (+,×) carrier on weighted
    DAGs: single-source path-count queries served by the batched
    accumulate-form fixpoint (dense and CSR) vs the tuple engine evaluating
    the same magic-restricted program per query; fast-path answers are
    checked against the tuple engine's EXACT integer counts.

  * ``async``       — ``--async``: the continuous-batching admission
    front-end under open-loop Poisson load.  A load generator submits
    single queries on a fixed Poisson arrival schedule swept across offered
    rates (multiples of the measured sync one-at-a-time qps); the
    dispatcher coalesces the arrivals into batched fixpoints.  Per rate:
    achieved qps, shed count, and p50/p95/p99 latency — the
    throughput–latency curve.

  * ``obs``         — ``--obs``: observability cost + per-stage latency
    breakdown.  Steady-state qps with the unified metrics registry
    default-ON vs OFF (acceptance: ON >= 0.95x OFF), then an async run on
    a traced+metered service reporting queue-wait / device / finalize
    percentiles from the stage histograms and checking the exported Chrome
    trace shows dispatcher-lane launches overlapping finalizer-lane
    finalizes (the PR-6 double-buffering, now visible in a timeline).
    ``--trace-out`` / ``--metrics-out`` export that run's artifacts.

  * ``durable``     — ``--durable``: restart time-to-first-answer.  A
    durable service populated under ``snapshot_every=1`` crashes; a warm
    restart (``durable_dir=`` recovery) and a cold in-memory rebuild race
    to the same answer batches, each in a FRESH interpreter (a real
    restart has a cold jit cache: cold pays compile + fixpoints, warm
    serves from the restored answer cache and runs no fixpoint).  Then a
    WAL-suffix crash (snapshot behind; records replayed via
    append-resume) and a torn-WAL-tail restart must both serve exact
    answers for everything but the torn append.

Acceptance (ISSUE 2): steady-state B=32 serving >= 5x sequential
``Engine.ask`` qps; append-resume beats recompute.
Acceptance (ISSUE 4): steady-state B=16 tuple-batch >= 3x sequential
``Engine.ask`` qps; warm tuple batches skip re-tracing (asserted in smoke).
Acceptance (ISSUE 5): on sparse G4096 (p≈0.002) the batched CSR frontier
fixpoint serves >= 3x dense steady-state qps at B=32, answers bit-identical,
``fixpoint_trace_count`` stable across warm CSR batches.
Acceptance (ISSUE 9): the counting fast path serves >= 3x the tuple
engine's steady qps on the G1024/G4096 DAG workloads with exact integer
counts; smoke asserts fast-path >= tuple-engine qps.
Acceptance (ISSUE 6): under Poisson load on the G1024 TC workload the async
front-end sustains >= 2.5x the sync one-at-a-time steady qps while p99
latency stays <= 5x the single-query service time; smoke asserts >= 1.5x
and flat ``fixpoint_trace_count`` across warm flushes.
Acceptance (ISSUE 10): warm restart from snapshot+WAL >= 5x faster than
cold rebuild to first answer on the G1024 TC workload, answers
bit-identical to the crashed service; torn-tail recovery serves exact
answers; smoke asserts warm < cold.

Usage:  PYTHONPATH=src python benchmarks/bench_serve.py [--smoke] [--out F]
        ... --sparse   run ONLY the sparse-vs-dense section and merge it
                       into the existing BENCH_serve.json (prints on smoke)
        ... --async    run ONLY the admission front-end rate sweep and merge
                       it the same way
"""
from __future__ import annotations

import argparse
import json
import threading
import time
from pathlib import Path

import numpy as np

from common import latency_percentiles, poisson_arrivals
from repro.core import engine as engine_mod
from repro.service import batch as batch_mod
from repro.core.engine import Engine
from repro.data.graphs import gnp_graph, tree_graph
from repro.service import (AsyncDatalogService, DatalogService,
                           QueueFullError)

TC = """
tc(X,Y) <- arc(X,Y).
tc(X,Y) <- tc(X,Z), arc(Z,Y).
"""

SG = """
sg(X,Y) <- arc(P,X), arc(P,Y), X != Y.
sg(X,Y) <- arc(A,X), sg(A,B), arc(B,Y).
"""


def _wall(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def rows_set(rows):
    return {tuple(map(int, r)) for r in rows}


def bench(smoke: bool) -> dict:
    if smoke:
        n, p, n_queries, n_append = 128, 0.05, 8, 16
    else:
        n, p, n_queries, n_append = 1024, 0.01, 256, 64
    edges = gnp_graph(n, p, seed=11)
    rng = np.random.default_rng(5)
    sources = rng.choice(n, size=n_queries, replace=False).tolist()
    rec: dict = {"graph": f"G{n}-p{p}", "edges": int(len(edges)),
                 "queries": n_queries, "smoke": smoke}
    print(f"{rec['graph']}: {rec['edges']} edges, {n_queries} query sources",
          flush=True)
    if not smoke:
        assert len(edges) >= 10_000, "acceptance wants a >= 10k-edge workload"

    ask_caps = dict(default_cap=1 << 12 if smoke else 1 << 13,
                    join_cap=1 << 13 if smoke else 1 << 15)

    # --- sequential Engine.ask ------------------------------------------------
    seq_n = min(32, n_queries)
    eng = Engine(TC, db={"arc": edges}, **ask_caps)
    _, t_first = _wall(lambda: eng.ask("tc", (sources[0], None)))
    _, t_seq = _wall(lambda: [eng.ask("tc", (s, None))
                              for s in sources[1:seq_n]])
    rec["sequential"] = {
        "queries": seq_n - 1,
        "first_query_seconds": t_first,  # includes the one-off compile
        "seconds": t_seq,
        "qps": (seq_n - 1) / t_seq,
    }
    print(f"  sequential ask: first {t_first:.3f}s, then "
          f"{rec['sequential']['qps']:.1f} qps", flush=True)

    # --- service at batch sizes ----------------------------------------------
    rec["service"] = []
    for b in (1, 32, 256):
        if b > n_queries:
            continue
        svc = DatalogService(TC, db={"arc": edges}, **ask_caps)
        cold_q = [("tc", (s, None)) for s in sources[:b]]
        cold_res, t_cold = _wall(lambda: svc.ask_batch(cold_q))
        # steady state: compile-warm service, result-cache-cold sources
        if 2 * b <= n_queries:
            steady_q = [("tc", (s, None)) for s in sources[b:2 * b]]
            _, t_steady = _wall(lambda: svc.ask_batch(steady_q))
        else:  # not enough distinct sources: re-measure on a cleared cache
            # (the batched fixpoint shape is compile-warm from the cold run)
            svc.cache.clear()
            _, t_steady = _wall(lambda: svc.ask_batch(cold_q))
        _, t_warm = _wall(lambda: svc.ask_batch(cold_q))  # pure cache hits
        rec["service"].append({
            "batch": b,
            "cold_seconds": t_cold, "cold_qps": b / t_cold,
            "steady_seconds": t_steady, "steady_qps": b / t_steady,
            "warm_seconds": t_warm, "warm_qps": b / t_warm,
        })
        print(f"  service B={b:3d}: cold {b / t_cold:8.1f} qps, "
              f"steady {b / t_steady:8.1f} qps, warm {b / t_warm:8.1f} qps",
              flush=True)
        # spot-check against the sequential path
        assert rows_set(cold_res[0]) == rows_set(
            eng.ask("tc", (sources[0], None)))

    b32 = next((s for s in rec["service"] if s["batch"] == 32), None)
    if b32 is not None:
        rec["speedup_b32_vs_sequential"] = \
            b32["steady_qps"] / rec["sequential"]["qps"]
        print(f"  B=32 steady vs sequential: "
              f"{rec['speedup_b32_vs_sequential']:.1f}x", flush=True)

    # --- append-resume vs recompute ------------------------------------------
    nb = min(32, n_queries)
    warmup_edges = np.stack([rng.integers(0, n, n_append),
                             rng.integers(0, n, n_append)], axis=1)
    new_edges = np.stack([rng.integers(0, n, n_append),
                          rng.integers(0, n, n_append)], axis=1)
    warm = DatalogService(TC, db={"arc": edges}, **ask_caps)
    warm.ask_batch([("tc", (s, None)) for s in sources[:nb]])  # populate cache
    # appends recur in a serving session: the first one pays the one-off
    # scatter/gather compiles; measure the steady state.  End-to-end cost of
    # an append = maintenance (resume cached closures) + re-serving the hot
    # sources from the refreshed cache.
    _, t_first_append = _wall(lambda: warm.append("arc", warmup_edges))
    _, t_resume = _wall(lambda: warm.append("arc", new_edges))
    resumed_res, t_reserve = _wall(
        lambda: warm.ask_batch([("tc", (s, None)) for s in sources[:nb]]))

    appended = np.concatenate([edges, warmup_edges, new_edges])
    cold = DatalogService(TC, db={"arc": appended}, **ask_caps)
    cold.ask_batch([("tc", (s, None)) for s in sources[nb:nb + nb]]
                   if 2 * nb <= n_queries else
                   [("tc", (sources[-1], None))])  # compile-warm
    cold.cache.clear()
    recompute_res, t_recompute = _wall(
        lambda: cold.ask_batch([("tc", (s, None)) for s in sources[:nb]]))
    # the resumed cache must agree with the from-scratch recompute
    for s, res, want in zip(sources[:nb], resumed_res, recompute_res):
        assert rows_set(res) == rows_set(want), s
    rec["append"] = {
        "appended_edges": int(n_append),
        "cached_sources": nb,
        "first_append_seconds": t_first_append,  # one-off compiles included
        "resume_seconds": t_resume,  # maintenance: delta-frontier fixpoint
        "reserve_seconds": t_reserve,  # serving the burst from refreshed cache
        "recompute_seconds": t_recompute,  # cacheless: burst from scratch
        "speedup": t_recompute / (t_resume + t_reserve),
    }
    print(f"  append: resume {t_resume:.3f}s + serve {t_reserve:.3f}s vs "
          f"recompute {t_recompute:.3f}s ({rec['append']['speedup']:.1f}x)",
          flush=True)

    # --- qid-batched tuple-path fixpoints (non-decomposable predicate) --------
    bt = 8 if smoke else 16
    height = 4 if smoke else 5
    sg_edges = tree_graph(height, seed=7, min_deg=3, max_deg=4)
    nverts = int(sg_edges.max()) + 1
    srng = np.random.default_rng(17)
    sg_sources = srng.choice(nverts // 2, size=3 * bt, replace=False) \
        + nverts // 3  # mid-tree vertices: non-trivial generations
    # the union of B demands needs headroom over a single query's tables
    sg_caps = dict(default_cap=1 << 12 if smoke else 1 << 14,
                   join_cap=1 << 14 if smoke else 1 << 16,
                   caps={} if smoke else {"sg": 1 << 16})
    sg_eng = Engine(SG, db={"arc": sg_edges}, **sg_caps)
    _, t_sg_first = _wall(lambda: sg_eng.ask("sg", (int(sg_sources[0]), None)))
    seq_ref, t_sg_seq = _wall(lambda: [sg_eng.ask("sg", (int(s), None))
                                       for s in sg_sources[1:bt + 1]])
    svc_sg = DatalogService(SG, db={"arc": sg_edges}, **sg_caps)
    cold_q = [("sg", (int(s), None)) for s in sg_sources[1:bt + 1]]
    cold_res, t_bt_cold = _wall(lambda: svc_sg.ask_batch(cold_q))
    steady_q = [("sg", (int(s), None)) for s in sg_sources[bt + 1:2 * bt + 1]]
    _, t_bt_steady = _wall(lambda: svc_sg.ask_batch(steady_q))
    _, t_bt_warm = _wall(lambda: svc_sg.ask_batch(cold_q))  # cache hits
    for s, res, want in zip(sg_sources[1:bt + 1], cold_res, seq_ref):
        assert rows_set(res) == rows_set(want), s  # batched == sequential
    assert svc_sg.stats.tuple_fixpoints >= 1
    if smoke:
        # warm tuple batches provably skip re-tracing: identical shapes on a
        # cleared result cache must not move the trace counter
        svc_sg.cache.clear()
        t0 = engine_mod.fixpoint_trace_count()
        svc_sg.ask_batch(cold_q)
        assert engine_mod.fixpoint_trace_count() == t0, \
            "warm tuple batch re-traced a compiled fixpoint"
    rec["tuple_batch"] = {
        "graph": f"tree-h{height}", "edges": int(len(sg_edges)),
        "batch": bt,
        "sequential_qps": bt / t_sg_seq,
        "sequential_first_seconds": t_sg_first,
        "cold_seconds": t_bt_cold, "cold_qps": bt / t_bt_cold,
        "steady_seconds": t_bt_steady, "steady_qps": bt / t_bt_steady,
        "warm_seconds": t_bt_warm, "warm_qps": bt / t_bt_warm,
        "speedup_steady_vs_sequential": t_sg_seq / t_bt_steady,
    }
    print(f"  tuple batch B={bt}: sequential {bt / t_sg_seq:7.1f} qps, "
          f"steady {bt / t_bt_steady:7.1f} qps "
          f"({rec['tuple_batch']['speedup_steady_vs_sequential']:.1f}x), "
          f"warm {bt / t_bt_warm:8.1f} qps", flush=True)
    return rec


def bench_sparse(smoke: bool) -> dict:
    """CSR-vs-dense steady-state serving on a sparse Gn-p workload.

    Both services run the same batched closure path (``ask_batch`` ->
    ``_run_dense_batch``); only the representation differs — the dense one
    multiplies the (n_alloc, n_alloc) matrix every iteration, the CSR one
    runs the O(|E|) segment step over packed arcs.  Steady state = second
    batch of fresh sources (compile-warm, result-cache cold).
    """
    if smoke:
        # 2048 nodes, not 1024: after the host-finalize fix a 1024-node
        # batch is launch-overhead-bound and dense ties CSR (the compare
        # was a coin flip); at 2048/p=0.002 CSR wins ~1.8x reproducibly
        n, p, b = 2048, 0.002, 16
    else:
        n, p, b = 4096, 0.002, 32
    edges = gnp_graph(n, p, seed=23)
    rng = np.random.default_rng(29)
    sources = rng.choice(n, size=3 * b, replace=False).tolist()
    density = len(edges) / float(n * n)
    rec: dict = {"graph": f"G{n}-p{p}", "edges": int(len(edges)),
                 "density": density, "batch": b, "smoke": smoke}
    print(f"sparse: {rec['graph']}, {rec['edges']} edges "
          f"(density {density:.2e}), B={b}", flush=True)
    sides = {}
    for name, flag in (("dense", False), ("csr", True)):
        svc = DatalogService(TC, db={"arc": edges}, sparse=flag)
        cold_q = [("tc", (s, None)) for s in sources[:b]]
        res_cold, t_cold = _wall(lambda: svc.ask_batch(cold_q))
        steady_q = [("tc", (s, None)) for s in sources[b:2 * b]]
        res_steady, t_steady = _wall(lambda: svc.ask_batch(steady_q))
        for _ in range(2):
            # best-of-3: a steady batch is ~10 ms of mostly launch overhead,
            # so a single-sample timing jitters enough to flip the compare
            svc.cache.clear()
            _, t_again = _wall(lambda: svc.ask_batch(steady_q))
            t_steady = min(t_steady, t_again)
        # warm-shape stability: a third batch of fresh sources hits the same
        # padded (B, n_alloc) fixpoint shape — zero re-traces
        t0 = engine_mod.fixpoint_trace_count()
        svc.ask_batch([("tc", (s, None)) for s in sources[2 * b:3 * b]])
        assert engine_mod.fixpoint_trace_count() == t0, \
            f"warm {name} batch re-traced a compiled fixpoint"
        assert (svc.stats.csr_fixpoints > 0) == flag  # routed as forced
        sides[name] = {"svc": svc, "cold": res_cold, "steady": res_steady}
        rec[name] = {"cold_seconds": t_cold, "cold_qps": b / t_cold,
                     "steady_seconds": t_steady, "steady_qps": b / t_steady}
        print(f"  {name:5s}: cold {b / t_cold:8.1f} qps, "
              f"steady {b / t_steady:8.1f} qps", flush=True)
    for kind in ("cold", "steady"):  # dense-vs-CSR answers bit-identical
        for a, c in zip(sides["dense"][kind], sides["csr"][kind]):
            assert np.array_equal(a, c), "dense/CSR answers diverged"
    rec["speedup_csr_vs_dense_steady"] = \
        rec["csr"]["steady_qps"] / rec["dense"]["steady_qps"]
    print(f"  CSR vs dense steady: "
          f"{rec['speedup_csr_vs_dense_steady']:.1f}x", flush=True)
    if smoke:
        assert rec["speedup_csr_vs_dense_steady"] >= 1.0, \
            "CSR slower than dense on the sparse smoke workload"
    else:
        assert rec["speedup_csr_vs_dense_steady"] >= 3.0, \
            "acceptance: CSR >= 3x dense steady qps on sparse G4096"
    return rec


CPATH = """
cpath(X,Z,sum<C>) <- d(X,Z,C).
cpath(X,Z,sum<C>) <- cpath(X,Y,C1), d(Y,Z,C2), C = C1 * C2.
"""


def agg_map(res):
    rows, vals = res
    return {tuple(map(int, r)): int(v) for r, v in zip(rows, vals)}


def bench_counting(smoke: bool) -> dict:
    """Counting (plus-times) fast path vs the tuple engine on weighted DAGs.

    Workload: single-source path-count queries (``cpath``, unit weights —
    the closure IS the number of distinct paths) on random DAGs at average
    out-degree ~4 (``p = 8/n`` over the upper triangle keeps per-source
    count totals around e^{pn} ≈ 3k, far inside f32's exact-integer range).
    The tuple engine evaluates the same magic-restricted program a query at
    a time; the fast path runs the batched accumulate-form fixpoint on the
    dense and CSR carriers.  Every fast-path answer is checked against the
    tuple engine's EXACT integer counts — never fp-tolerant.
    """
    from repro.data.graphs import dag_graph
    if smoke:
        sizes, b, seq_n = [256], 8, 4
    else:
        sizes, b, seq_n = [1024, 4096], 32, 8
    rec: dict = {"smoke": smoke, "workloads": []}
    for n in sizes:
        p = 8.0 / n
        edges = dag_graph(n, p, seed=31)
        rng = np.random.default_rng(37)
        # sources in the lower half of the topological order: real fan-out
        sources = rng.choice(n // 2, size=3 * b, replace=False).tolist()
        wl: dict = {"graph": f"dag-G{n}-p{p:.4f}", "n": n,
                    "edges": int(len(edges)), "batch": b}
        print(f"counting: {wl['graph']}, {wl['edges']} arcs, B={b}",
              flush=True)

        # --- tuple engine: one magic-restricted ask per query -----------------
        eng = Engine(CPATH, db={"d": edges}, default_cap=1 << 13,
                     join_cap=1 << 15)
        _, t_first = _wall(
            lambda: eng.ask("cpath", (sources[0], None, None)))
        tuple_ref, t_tuple = _wall(
            lambda: [eng.ask("cpath", (s, None, None))
                     for s in sources[1:seq_n + 1]])
        wl["tuple_engine"] = {"queries": seq_n,
                              "first_query_seconds": t_first,
                              "seconds": t_tuple, "qps": seq_n / t_tuple}
        print(f"  tuple engine: first {t_first:.3f}s, then "
              f"{wl['tuple_engine']['qps']:8.1f} qps", flush=True)

        # --- fast path: batched accumulate fixpoint, both carriers ------------
        for name, flag in (("dense", False), ("csr", True)):
            svc = DatalogService(CPATH, db={"d": edges}, sparse=flag)
            cold_q = [("cpath", (s, None, None)) for s in sources[:b]]
            res_cold, t_cold = _wall(lambda: svc.ask_batch(cold_q))
            steady_q = [("cpath", (s, None, None))
                        for s in sources[b:2 * b]]
            _, t_steady = _wall(lambda: svc.ask_batch(steady_q))
            for _ in range(2):  # best-of-3: steady batches are ms-scale
                svc.cache.clear()
                _, t_again = _wall(lambda: svc.ask_batch(steady_q))
                t_steady = min(t_steady, t_again)
            # warm-shape stability: fresh sources, same padded shape
            t0 = engine_mod.fixpoint_trace_count()
            svc.ask_batch([("cpath", (s, None, None))
                           for s in sources[2 * b:3 * b]])
            assert engine_mod.fixpoint_trace_count() == t0, \
                f"warm {name} counting batch re-traced a compiled fixpoint"
            assert (svc.stats.csr_fixpoints > 0) == flag
            assert svc.explain()["relations"]["cpath"]["semiring"] == \
                "plus_times"
            # oracle: exact integer counts vs the tuple engine
            for s, got in zip(sources[1:seq_n + 1],
                              svc.ask_batch([("cpath", (s, None, None))
                                             for s in
                                             sources[1:seq_n + 1]])):
                want = tuple_ref[sources[1:seq_n + 1].index(s)]
                assert agg_map(got) == agg_map(want), \
                    f"{name} fast path diverged from exact counts at src {s}"
            wl[name] = {"cold_seconds": t_cold, "cold_qps": b / t_cold,
                        "steady_seconds": t_steady,
                        "steady_qps": b / t_steady}
            print(f"  {name:5s}: cold {b / t_cold:8.1f} qps, "
                  f"steady {b / t_steady:8.1f} qps", flush=True)
        fast = max(wl["dense"]["steady_qps"], wl["csr"]["steady_qps"])
        wl["speedup_fast_vs_tuple"] = fast / wl["tuple_engine"]["qps"]
        print(f"  fast path vs tuple engine: "
              f"{wl['speedup_fast_vs_tuple']:.1f}x", flush=True)
        if smoke:
            assert wl["speedup_fast_vs_tuple"] >= 1.0, \
                "smoke: counting fast path slower than the tuple engine"
        else:
            assert wl["speedup_fast_vs_tuple"] >= 3.0, \
                f"acceptance: counting fast path >= 3x tuple-engine " \
                f"steady qps on G{n}"
        rec["workloads"].append(wl)
    return rec


def _run_level(front, queries, arrivals):
    """Drive one open-loop load level: submit each query at its scheduled
    arrival instant, record per-query latency via done-callbacks (so the
    generator never blocks on results), drain, and summarize."""
    lats: list = [None] * len(queries)
    shed = 0
    t0 = time.perf_counter()
    for i, (q, at) in enumerate(zip(queries, arrivals)):
        now = time.perf_counter() - t0
        if at > now:
            time.sleep(at - now)
        t_sub = time.perf_counter()
        try:
            fut = front.submit(q)
        except QueueFullError:
            shed += 1
            continue
        fut.add_done_callback(
            lambda f, i=i, t=t_sub: lats.__setitem__(
                i, time.perf_counter() - t))
    front.drain(timeout=300.0)
    elapsed = time.perf_counter() - t0
    served = len(queries) - shed
    return {
        "offered_qps": len(queries) / float(arrivals[-1]),
        "achieved_qps": served / elapsed,
        "served": served, "shed": shed,
        **latency_percentiles(lats),
    }


def bench_async(smoke: bool) -> dict:
    """Throughput–latency curve of the admission front-end under open-loop
    Poisson load (single-source TC on the G1024 workload of ``bench``).

    Baseline = sync one-at-a-time ``DatalogService.ask`` over the same
    source distribution and cache config; ``service_seconds`` = median
    latency of a single cache-miss query on the compile-warm service (the
    denominator of the p99 <= 5x acceptance bound).  The sweep offers
    Poisson arrivals at multiples of the baseline qps; between levels the
    result cache is cleared so every level starts cache-cold like the
    baseline did.
    """
    if smoke:
        n, p, n_level, mults = 128, 0.05, 48, (1.0, 2.0, 3.0)
        max_wait_ms, max_batch = 2.0, 16
    else:
        n, p, n_level, mults = 1024, 0.01, 384, (0.5, 1.0, 2.0, 4.0, 8.0)
        # max_batch=16, not 32: a 32-wide G1024 flush runs ~50 ms on device,
        # blowing the p99 <= 5x-service-time bound all by itself; 16 keeps
        # per-flush latency inside the bound at a small throughput cost
        max_wait_ms, max_batch = 2.0, 16
    edges = gnp_graph(n, p, seed=11)
    rng = np.random.default_rng(41)
    rec: dict = {"graph": f"G{n}-p{p}", "edges": int(len(edges)),
                 "queries_per_level": n_level, "smoke": smoke,
                 "max_wait_ms": max_wait_ms, "max_batch": max_batch}
    print(f"async: {rec['graph']}, {rec['edges']} edges, "
          f"{n_level} queries/level", flush=True)

    def sample(k):  # with replacement: repeats model a hot-source skew
        return [("tc", (int(s), None)) for s in rng.integers(0, n, size=k)]

    # --- sync one-at-a-time baseline (same cache config, same distribution)
    base = DatalogService(TC, db={"arc": edges})
    for q in sample(4):
        base.ask(q)  # compile-warm prelude
    base.cache.clear()
    base_q = sample(n_level)
    t0 = time.perf_counter()
    for q in base_q:
        base.ask(q)
    t_base = time.perf_counter() - t0
    base_qps = n_level / t_base
    # single-query service time: median cache-miss latency, compile-warm
    svc_times = []
    for q in sample(9):
        base.cache.clear()
        _, dt = _wall(lambda: base.ask(q))
        svc_times.append(dt)
    t_service = float(np.median(svc_times))
    rec["sync"] = {"qps": base_qps, "seconds": t_base,
                   "service_seconds": t_service}
    print(f"  sync one-at-a-time: {base_qps:.1f} qps, single-query service "
          f"{t_service * 1e3:.2f} ms", flush=True)

    # --- open-loop Poisson sweep over offered rates
    front = AsyncDatalogService(
        DatalogService(TC, db={"arc": edges}),
        max_wait_ms=max_wait_ms, max_batch=max_batch, queue_depth=512)
    # compile-warm every pad shape a flush can hit — arrival-dependent flush
    # sizes quantize to batch_pads, and a mid-sweep ~1s XLA compile would
    # swamp a whole level's latency distribution
    top = batch_mod.pad_batch_size(max_batch, front.svc.batch_pads)
    for b in [lv for lv in front.svc.batch_pads if lv <= top]:
        front.svc.ask_batch(
            [("tc", (int(s), None))
             for s in rng.choice(n, size=b, replace=False)])
    rec["levels"] = []
    for i, m in enumerate(mults):
        with front.svc.lock:
            front.svc.cache.clear()  # every level starts cache-cold
        level = _run_level(front, sample(n_level),
                           poisson_arrivals(m * base_qps, n_level, seed=61 + i))
        level["rate_multiple"] = m
        rec["levels"].append(level)
        print(f"  offered {level['offered_qps']:8.1f} qps ({m:4.1f}x sync): "
              f"achieved {level['achieved_qps']:8.1f} qps, "
              f"p50 {level['p50'] * 1e3:7.2f} ms, "
              f"p99 {level['p99'] * 1e3:7.2f} ms, shed {level['shed']}",
              flush=True)

    # --- warm-flush shape stability: same pad level, fresh sources, zero
    # re-traces (the dispatcher pads flushes to the service's batch_pads)
    burst = [("tc", (int(s), None))
             for s in rng.choice(n, size=max_batch, replace=False)]
    with front.svc.lock:
        front.svc.cache.clear()
    front.ask_batch(burst)
    with front.svc.lock:
        front.svc.cache.clear()
    t0 = engine_mod.fixpoint_trace_count()
    front.ask_batch(burst)
    retraced = engine_mod.fixpoint_trace_count() - t0
    assert retraced == 0, "warm async flush re-traced a compiled fixpoint"
    rec["warm_flush_retraces"] = retraced

    peak = max(rec["levels"], key=lambda lv: lv["achieved_qps"])
    rec["speedup_vs_sync"] = peak["achieved_qps"] / base_qps
    best = max((lv for lv in rec["levels"]
                if lv["p99"] is not None and lv["p99"] <= 5.0 * t_service),
               key=lambda lv: lv["achieved_qps"], default=None)
    rec["best_within_latency_bound"] = best
    print(f"  peak achieved: {peak['achieved_qps']:.1f} qps "
          f"({rec['speedup_vs_sync']:.1f}x sync)", flush=True)
    if best is not None:
        rec["speedup_within_bound_vs_sync"] = best["achieved_qps"] / base_qps
        print(f"  best within p99 <= 5x service time "
              f"({5e3 * t_service:.1f} ms): {best['achieved_qps']:.1f} qps "
              f"({rec['speedup_within_bound_vs_sync']:.1f}x sync)", flush=True)
    front.close()
    if smoke:  # smoke gate: throughput + warm-shape stability only — the
        # p99 bound is a G1024 acceptance criterion; on the tiny smoke graph
        # the coalescing window itself dwarfs the sub-ms service time
        assert rec["speedup_vs_sync"] >= 1.5, \
            "smoke: async must sustain >= 1.5x sync one-at-a-time qps"
    else:
        assert best is not None and \
            rec["speedup_within_bound_vs_sync"] >= 2.5, \
            "acceptance: async >= 2.5x sync one-at-a-time qps at p99 <= " \
            "5x single-query service time"
    return rec


def bench_obs(smoke: bool, trace_out: str | None = None,
              metrics_out: str | None = None) -> dict:
    """Observability cost + per-stage latency attribution.

    Three measurements on the TC serving workload:

    * **overhead** — steady-state batched qps with the unified metrics
      registry default-ON vs ``metrics=False``, best-of-k each on the same
      compile-warm shapes and source batch; acceptance is ON >= 0.95x OFF
      (default-on metrics cost <= 5%).
    * **stages** — an async run against a traced + metered service: the
      stage histograms give the queue-wait / device / finalize latency
      breakdown the flat qps number hides.
    * **overlap** — the same run's trace must show a dispatcher-lane
      ``launch_batch`` span overlapping a finalizer-lane ``finalize_batch``
      span: the admission front-end's device/host double-buffering, visible
      in the exported Chrome timeline.
    """
    if smoke:
        n, p, b, n_async, repeats = 128, 0.05, 16, 64, 15
    else:
        n, p, b, n_async, repeats = 1024, 0.01, 32, 256, 5
    edges = gnp_graph(n, p, seed=11)
    rng = np.random.default_rng(47)
    rec: dict = {"graph": f"G{n}-p{p}", "edges": int(len(edges)),
                 "batch": b, "smoke": smoke}
    print(f"obs: {rec['graph']}, {rec['edges']} edges, B={b}", flush=True)

    # --- default-on metrics overhead vs metrics=False ------------------------
    # interleaved best-of-k over BLOCKS of batches: a single steady batch is
    # ms-scale, where timer jitter and background drift dwarf a few-percent
    # metrics cost.  Each sample times `block` back-to-back cache-cleared
    # batches, and the two sides alternate rounds so slow periods hit both.
    block = 8 if smoke else 4
    sources = rng.choice(n, size=2 * b, replace=False).tolist()
    cold_q = [("tc", (s, None)) for s in sources[:b]]
    steady_q = [("tc", (s, None)) for s in sources[b:2 * b]]
    svcs = {"metrics_off": DatalogService(TC, db={"arc": edges},
                                          metrics=False),
            "metrics_on": DatalogService(TC, db={"arc": edges})}
    t_best = {name: None for name in svcs}
    for svc in svcs.values():
        assert len(svc.ask_batch(cold_q)) == b  # compile-warm prelude

    def run_block(svc):
        for _ in range(block):
            svc.cache.clear()
            svc.ask_batch(steady_q)

    for _ in range(repeats):
        for name, svc in svcs.items():
            _, t = _wall(lambda: run_block(svc))
            t_best[name] = t if t_best[name] is None \
                else min(t_best[name], t)
    for name, t_block in t_best.items():
        t_steady = t_block / block
        rec[name] = {"steady_seconds": t_steady, "steady_qps": b / t_steady}
        print(f"  {name:11s}: steady {b / t_steady:8.1f} qps", flush=True)
    rec["metrics_on_over_off"] = (rec["metrics_on"]["steady_qps"]
                                  / rec["metrics_off"]["steady_qps"])
    print(f"  metrics on/off qps ratio: {rec['metrics_on_over_off']:.3f}",
          flush=True)
    assert rec["metrics_on_over_off"] >= 0.95, \
        "acceptance: default-on metrics must cost <= 5% steady qps"

    # --- traced + metered async run: stage breakdown + overlap ---------------
    max_batch = 8
    svc = DatalogService(TC, db={"arc": edges}, tracer=True)
    front = AsyncDatalogService(svc, max_wait_ms=1.0, max_batch=max_batch,
                                queue_depth=1024)
    # compile-warm every pad shape a flush can hit, then trace a clean run
    top = batch_mod.pad_batch_size(max_batch, svc.batch_pads)
    for bb in [lv for lv in svc.batch_pads if lv <= top]:
        svc.ask_batch([("tc", (int(s), None))
                       for s in rng.choice(n, size=bb, replace=False)])
    with svc.lock:
        svc.cache.clear()
    svc.tracer.clear()
    burst = [("tc", (int(s), None)) for s in rng.integers(0, n, size=n_async)]
    t0 = time.perf_counter()
    futs = [front.submit(q) for q in burst]
    front.drain(timeout=300.0)
    elapsed = time.perf_counter() - t0
    assert all(f.done() for f in futs)
    rec["async_traced"] = {"queries": n_async, "max_batch": max_batch,
                           "seconds": elapsed, "qps": n_async / elapsed}
    m = svc.metrics
    rec["stages"] = {
        "queue_wait": m.histogram("datalog_queue_wait_seconds").percentiles(),
        "device": m.histogram("datalog_device_seconds").percentiles(),
        "finalize": m.histogram("datalog_finalize_seconds").percentiles(),
    }
    for stage, pcts in rec["stages"].items():
        print(f"  {stage:10s}: " + "  ".join(
            f"{k} {v * 1e3:7.3f} ms" for k, v in pcts.items()), flush=True)

    launches = svc.tracer.spans("launch_batch")
    finals = svc.tracer.spans("finalize_batch")
    overlap = sum(1 for lb in launches for fb in finals
                  if lb["tid"] != fb["tid"] and svc.tracer.overlaps(lb, fb))
    rec["trace"] = {"events": len(svc.tracer.events()),
                    "launches": len(launches), "finalizes": len(finals),
                    "launch_finalize_overlaps": overlap}
    print(f"  trace: {rec['trace']['events']} events, "
          f"{len(launches)} launches, {overlap} cross-lane "
          f"launch/finalize overlaps", flush=True)
    if not smoke:  # a 256-query burst over >= 32 flushes must pipeline
        assert overlap > 0, \
            "async trace shows no launch/finalize double-buffering overlap"
    if metrics_out:
        m.export(metrics_out)
        print(f"  metrics -> {metrics_out}", flush=True)
    if trace_out:
        svc.tracer.export_chrome(trace_out)
        print(f"  trace -> {trace_out}", flush=True)
    front.close()
    return rec


# child script for the restart race: a REAL restart is a fresh process with
# a cold jit cache, so each side runs in its own interpreter.  Timing starts
# after imports (interpreter + jax import cost is common to both) and covers
# service construction -> last answer of the batch set: the cold side pays
# engine build + fixpoint compilation + every closure fixpoint; the warm
# side pays snapshot load + restore + (possibly) WAL replay.
_DURABLE_CHILD = r"""
import json, sys, time
import numpy as np
cfg = json.loads(sys.argv[1])
from repro.service import DatalogService
TC = "tc(X,Y) <- arc(X,Y).\ntc(X,Y) <- tc(X,Z), arc(Z,Y)."
edb = np.load(cfg["edb"])
batches = [[("tc", (int(s), None)) for s in bb] for bb in cfg["batches"]]
kw = {"durable_dir": cfg["durable_dir"]} if cfg.get("durable_dir") else {}
t0 = time.perf_counter()
svc = DatalogService(TC, db={"arc": edb}, result_cache=4096, **kw)
answers = [svc.ask_batch(list(bb)) for bb in batches]
elapsed = time.perf_counter() - t0
out = {"seconds": elapsed}
if cfg.get("durable_dir"):
    out["recovery"] = svc.explain()["durability"]["recovery"]
np.savez(cfg["answers"], **{f"b{i}_{j}": np.asarray(a)
                            for i, bb in enumerate(answers)
                            for j, a in enumerate(bb)})
print("RESULT " + json.dumps(out))
"""


# child script that populates the durable state: a service under the
# max-durability cadence answers the batches, takes the appends, records the
# answers it then serves, and dies without close() — the crash.  It runs in
# its own interpreter so the benchmark's parent never holds the device while
# a child needs it.
_DURABLE_POPULATE = r"""
import json, os, sys
import numpy as np
cfg = json.loads(sys.argv[1])
from repro.service import DatalogService
TC = "tc(X,Y) <- arc(X,Y).\ntc(X,Y) <- tc(X,Z), arc(Z,Y)."
svc = DatalogService(TC, db={"arc": np.load(cfg["edb"])},
                     durable_dir=cfg["durable_dir"], snapshot_every=1,
                     result_cache=4096)
batches = [[("tc", (int(s), None)) for s in bb] for bb in cfg["batches"]]
for q in batches:
    svc.ask_batch(list(q))
for rows in cfg["appends"]:
    svc.append("arc", np.asarray(rows, np.int64))
svc._durable.wait()  # the auto-snapshot covers every append
want = [svc.ask_batch(list(q)) for q in batches]
np.savez(cfg["answers"], **{f"b{i}_{j}": np.asarray(a)
                            for i, bb in enumerate(want)
                            for j, a in enumerate(bb)})
print("RESULT {}", flush=True)
os._exit(0)  # crash: no close(), nothing was lost
"""


def _durable_child(cfg: dict, script: str = _DURABLE_CHILD
                   ) -> tuple[dict, dict]:
    """Run one child (populate, cold or warm restart) in a fresh
    interpreter; returns (timing/recovery record, {answer-key: rows})."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    src = str(Path(__file__).resolve().parent.parent / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", script, json.dumps(cfg)],
        capture_output=True, text=True, env=env, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"durable child failed:\n{proc.stderr[-2000:]}")
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    with np.load(cfg["answers"]) as z:
        answers = {k: z[k] for k in z.files}
    return json.loads(line[len("RESULT "):]), answers


def bench_durable(smoke: bool) -> dict:
    """``--durable``: restart time-to-first-answer, warm vs cold.

    Populate a durable service under the max-durability cadence
    (``snapshot_every=1``: every append publishes a snapshot, so a crash
    loses nothing and recovery is pure snapshot restore), crash it, then
    race two REAL restarts — fresh interpreters, cold jit caches — to the
    same batches of answers.  Populating runs in a child too: the parent
    touches no device until every child has exited, so on a chip (one
    process at a time) the children are not locked out:

    * **cold** — a fresh in-memory service over the final EDB: engine
      build + fixpoint compilation + every closure recomputed (what every
      restart cost before the durable layer);
    * **warm** — ``DatalogService(durable_dir=...)``: snapshot restore
      into the answer cache; serving then runs NO fixpoint at all, so the
      compile is skipped along with the compute — the Wisconsin-study
      point (arXiv 1812.03975) that materialized-state reuse dominates
      in-memory Datalog cost.

    Warm answers must be bit-identical to the crashed service's; the cold
    rebuild must agree as sets (its row order is its own).  Two in-process
    crash scenarios follow: a WAL-suffix crash (snapshot behind, records
    replayed through append-resume) and a torn WAL tail, both required to
    serve exact answers.  Acceptance: warm >= 5x cold on the full G1024 TC
    workload; smoke asserts warm beats cold.
    """
    import shutil
    import tempfile

    if smoke:
        n, p, b, nb = 128, 0.05, 16, 3
    else:
        n, p, b, nb = 1024, 0.01, 32, 4
    edges = gnp_graph(n, p, seed=11)
    rng = np.random.default_rng(53)
    srcs = rng.choice(n, size=b * nb, replace=False)
    batches = [[("tc", (int(s), None)) for s in srcs[i * b:(i + 1) * b]]
               for i in range(nb)]
    rec: dict = {"graph": f"G{n}-p{p}", "edges": int(len(edges)),
                 "batch": b, "batches": nb, "smoke": smoke}
    print(f"durable: {rec['graph']}, {rec['edges']} edges, "
          f"{nb} batches of {b}", flush=True)
    rows1 = np.asarray([[int(rng.integers(n)), int(rng.integers(n))]
                        for _ in range(8)], np.int64)
    rows2 = np.asarray([[int(rng.integers(n)), int(rng.integers(n))]
                        for _ in range(4)], np.int64)
    work = tempfile.mkdtemp(prefix="bench_durable_")
    dur = str(Path(work) / "state")
    try:
        genesis = Path(work) / "genesis.npy"
        final = Path(work) / "final.npy"
        np.save(genesis, edges)
        np.save(final,
                np.unique(np.concatenate([edges, rows1, rows2]), axis=0))
        src_lists = [[int(s) for s in srcs[i * b:(i + 1) * b]]
                     for i in range(nb)]

        # --- populate under snapshot_every=1, then crash -------------------
        _, want_ans = _durable_child(
            {"edb": str(genesis), "batches": src_lists, "durable_dir": dur,
             "appends": [rows1.tolist(), rows2.tolist()],
             "answers": str(Path(work) / "want.npz")}, _DURABLE_POPULATE)
        want = [[want_ans[f"b{i}_{j}"] for j in range(len(bb))]
                for i, bb in enumerate(batches)]

        # --- the race: fresh-process cold rebuild vs warm restart ----------
        cold_out, cold_ans = _durable_child(
            {"edb": str(final), "batches": src_lists,
             "answers": str(Path(work) / "cold.npz")})
        warm_out, warm_ans = _durable_child(
            {"edb": str(genesis), "batches": src_lists,
             "durable_dir": dur,
             "answers": str(Path(work) / "warm.npz")})
        assert warm_out["recovery"]["mode"] == "warm", warm_out
        for i, batch_want in enumerate(want):
            for j, w in enumerate(batch_want):
                assert np.array_equal(warm_ans[f"b{i}_{j}"],
                                      np.asarray(w)), \
                    "warm restart answers not bit-identical to crashed twin"
                assert rows_set(cold_ans[f"b{i}_{j}"]) == rows_set(w), \
                    "cold rebuild disagrees with the crashed twin"
        t_cold, t_warm = cold_out["seconds"], warm_out["seconds"]
        rec["cold_first_answer_seconds"] = t_cold
        rec["warm_first_answer_seconds"] = t_warm
        rec["warm_speedup"] = t_cold / t_warm
        rec["recovery"] = warm_out["recovery"]
        print(f"  cold rebuild : {t_cold:7.2f} s to last answer "
              "(fresh process: compile + fixpoints)", flush=True)
        print(f"  warm restart : {t_warm:7.2f} s to last answer "
              f"({rec['warm_speedup']:.1f}x; snapshot restore, no fixpoint)",
              flush=True)

        # --- crash with a WAL suffix: replay through append-resume ---------
        svc = DatalogService(TC, db={"arc": edges}, durable_dir=dur,
                             result_cache=4096)
        late = np.asarray([[int(rng.integers(n)), int(rng.integers(n))]
                           for _ in range(4)], np.int64)
        svc.append("arc", late)  # WALed, NOT snapshotted
        want_late = [svc.ask_batch(list(q)) for q in batches]
        del svc
        (svc_r, res_r), t_replay = _wall(lambda: (
            lambda s: (s, [s.ask_batch(list(q)) for q in batches]))(
            DatalogService(TC, db={"arc": edges}, durable_dir=dur,
                           result_cache=4096)))
        rep_r = svc_r.explain()["durability"]["recovery"]
        assert rep_r["mode"] == "warm" and rep_r["wal_replayed"] >= 1, rep_r
        for got_b, want_b in zip(res_r, want_late):
            for g, w in zip(got_b, want_b):
                assert np.array_equal(np.asarray(g), np.asarray(w)), \
                    "WAL-suffix recovery answers drifted"
        rec["wal_suffix"] = {"wal_replayed": rep_r["wal_replayed"],
                             "seconds": t_replay, "answers_correct": True}
        print(f"  WAL suffix   : {rep_r['wal_replayed']} records replayed "
              f"in {t_replay * 1e3:6.1f} ms (in-process), answers exact",
              flush=True)

        # --- torn WAL tail: lose the last append, stay correct -------------
        svc_r.append("arc", np.asarray([[0, n - 1]], np.int64))
        del svc_r  # crash again, then the disk tears the new record
        wal = Path(dur) / "wal.log"
        with open(wal, "r+b") as f:
            f.truncate(wal.stat().st_size - 6)
        svc_t = DatalogService(TC, db={"arc": edges}, durable_dir=dur,
                               result_cache=4096)
        rep_t = svc_t.explain()["durability"]["recovery"]
        assert rep_t["torn_bytes"] > 0, rep_t
        for got_b, want_b in zip(
                [svc_t.ask_batch(list(q)) for q in batches], want_late):
            for g, w in zip(got_b, want_b):  # pre-torn-append answers
                assert np.array_equal(np.asarray(g), np.asarray(w)), \
                    "torn-tail recovery answers drifted"
        rec["torn_tail"] = {"mode": rep_t["mode"],
                            "torn_bytes": rep_t["torn_bytes"],
                            "answers_correct": True}
        print(f"  torn tail    : {rep_t['torn_bytes']} bytes truncated, "
              f"recovered {rep_t['mode']}, answers exact", flush=True)
        svc_t.close()
    finally:
        shutil.rmtree(work, ignore_errors=True)
    assert rec["warm_speedup"] > 1.0, \
        "warm restart must beat cold rebuild to first answer"
    if not smoke:
        assert rec["warm_speedup"] >= 5.0, \
            "acceptance: warm restart >= 5x cold rebuild on G1024 TC"
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny instance for CI; does not write the JSON")
    ap.add_argument("--sparse", action="store_true",
                    help="run only the CSR-vs-dense sparse section and merge"
                         " it into the existing JSON")
    ap.add_argument("--counting", action="store_true",
                    help="run only the counting (plus-times) fast-path vs "
                         "tuple-engine section and merge it into the "
                         "existing JSON")
    ap.add_argument("--async", dest="use_async", action="store_true",
                    help="run only the admission front-end Poisson rate "
                         "sweep and merge it into the existing JSON")
    ap.add_argument("--obs", action="store_true",
                    help="run only the observability overhead/stage-breakdown"
                         " section and merge it into the existing JSON")
    ap.add_argument("--durable", action="store_true",
                    help="run only the durable restart section (warm "
                         "snapshot+WAL recovery vs cold rebuild, torn-tail "
                         "correctness) and merge it into the existing JSON")
    ap.add_argument("--trace-out", default=None, metavar="FILE.json",
                    help="with --obs: export the traced async run as a "
                         "Chrome trace_event timeline")
    ap.add_argument("--metrics-out", default=None, metavar="FILE",
                    help="with --obs: export the traced run's metrics "
                         "registry (.prom/.txt = Prometheus text, else JSON)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    out = Path(args.out) if args.out else Path(__file__).parent / "BENCH_serve.json"
    section = ("sparse", bench_sparse) if args.sparse else \
        ("counting", bench_counting) if args.counting else \
        ("async", bench_async) if args.use_async else \
        ("durable", bench_durable) if args.durable else \
        ("obs", lambda smoke: bench_obs(
            smoke, trace_out=args.trace_out,
            metrics_out=args.metrics_out)) if args.obs else None
    if section is not None:
        name, fn = section
        rec = fn(args.smoke)
        if args.smoke and args.out is None:
            print(json.dumps(rec, indent=2))
            return
        merged = json.loads(out.read_text()) if out.exists() else {}
        merged[name] = rec
        out.write_text(json.dumps(merged, indent=2))
        print(f"wrote {out} ({name} section)")
        return
    rec = bench(args.smoke)
    if args.smoke and args.out is None:
        print(json.dumps(rec, indent=2))
        return
    if out.exists():  # keep already-recorded per-flag sections
        prev = json.loads(out.read_text())
        for name in ("sparse", "counting", "async", "obs", "durable"):
            if name in prev:
                rec[name] = prev[name]
    out.write_text(json.dumps(rec, indent=2))
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
