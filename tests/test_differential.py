"""Differential property testing: every eval path == the naive reference.

Random programs (TC / nonlinear TC / same-generation / mutual recursion /
min-agg shortest paths, with random constants and repeated variables in the
goals) over random EDBs, checked against ``_reference.ref_model`` — a naive
fixpoint over Python sets — on TEN evaluation paths:

  1. naive full-model ``Engine.run()`` + goal filter
  2. ``Engine.ask``           (magic-sets restricted evaluation)
  3. ``Engine.ask`` magic=False  (demanded-strata fallback)
  4. ``DatalogService`` cached   (second batch = pure result-cache hits)
  5. ``DatalogService.ask_batch`` (dense micro-batch / qid-tagged tuple batch)
  6. append-resume               (serve, monotone append, re-serve)
  7. CSR-forced serving          (``sparse=True``: the packed O(|E|) frontier
                                 engine behind the same batching interface,
                                 batched + append-resume; answers must be
                                 bit-identical to the dense service's)
  8. async admission front-end   (``AsyncDatalogService``: the same queries
                                 submitted concurrently; the dispatcher's
                                 flush composition is timing-dependent, so
                                 answers are compared as sets — the invariant
                                 is that coalescing NEVER changes an answer)
  9. observed serving            (``tracer=True``: span recording must be
                                 bit-identical to the plain dense service,
                                 the fixpoint counters in ``explain()``
                                 must match the flushes' ``device_sync``
                                 spans and, on the bool carrier, balance
                                 seed + ΣΔ == final against the reference
                                 model, and re-serving a warm batch must
                                 neither launch nor retrace a fixpoint)
 10. tuned-kernel serving        (a pinned ``KernelConfig(use_kernel=True)``
                                 forces sliced-ELL + the Pallas tile-skip
                                 kernels on every CSR relation; answers must
                                 be bit-identical to the dense service's)
 11. counting fast path          (additive shapes only: the dense and CSR
                                 single-source count/sum closures equal the
                                 graph-level path-count oracle
                                 (``ref_path_counts``) exactly — integer
                                 counts compare exactly, never fp-tolerant)
 12. durable restart             (``durable_dir=``: kill the service between
                                 batches, restart, and require answers
                                 bit-identical to a never-restarted twin —
                                 snapshot+WAL warm recovery on even cases,
                                 pure WAL-replay cold recovery on odd ones,
                                 duplicate-append WAL replay every third)

The count/sum (``cpath``/``spath``) and max-plus (``lpath``) shapes draw
*acyclic* EDBs (arcs with src < dst): the additive (+,×) carrier has no
finite fixpoint on cycles — the serving path raises
``FixpointDivergenceError`` there by design — and the naive reference's
Jacobi recompute would not terminate either.

Case count defaults to a CI-smoke size; ``DIFF_CASES=200 pytest
tests/test_differential.py`` runs the acceptance-sized sweep (the generator
is deterministic per case index, so any failure reproduces by index).
``DIFF_SEED`` offsets the whole sweep.  Program *shapes* are fixed and small
so compiled fixpoints amortize across cases through the engine's runner
cache; only EDB rows, query constants and seeds vary.
"""
import os
import random
import tempfile
import threading

import numpy as np
import pytest
from _hypothesis_stub import HAVE_HYPOTHESIS, given, settings, st
from _reference import ref_answer, ref_model, ref_path_counts

from repro.core.engine import Engine
from repro.core.ir import Const, Literal, Var
from repro.service import AsyncDatalogService, DatalogService

DIFF_CASES = int(os.environ.get("DIFF_CASES", "16"))
DIFF_SEED = int(os.environ.get("DIFF_SEED", "0"))

SHAPES = {
    "tc": ("tc(X,Y) <- e(X,Y).\n"
           "tc(X,Y) <- tc(X,Z), e(Z,Y).", ["tc"], ("e",)),
    "tc_nl": ("tc(X,Y) <- e(X,Y).\n"
              "tc(X,Y) <- tc(X,Z), tc(Z,Y).", ["tc"], ("e",)),
    "sg": ("sg(X,Y) <- e(P,X), e(P,Y), X != Y.\n"
           "sg(X,Y) <- e(A,X), sg(A,B), e(B,Y).", ["sg"], ("e",)),
    "mutual": ("p(X,Y) <- e(X,Y).\n"
               "p(X,Y) <- q(X,Z), e(Z,Y).\n"
               "q(X,Y) <- f(X,Y).\n"
               "q(X,Y) <- p(X,Z), f(Z,Y).", ["p", "q"], ("e", "f")),
    "dpath": ("dpath(X,Z,min<D>) <- w(X,Z,D).\n"
              "dpath(X,Z,min<D>) <- dpath(X,Y,D1), w(Y,Z,D2), D = D1 + D2.",
              ["dpath"], ("w",)),
    # additive carriers: count/sum-in-recursion (plus-times) and longest
    # paths (max-plus), over the acyclic EDB relation "d" (src < dst)
    "cpath": ("cpath(X,Z,sum<C>) <- d(X,Z,C).\n"
              "cpath(X,Z,sum<C>) <- cpath(X,Y,C1), d(Y,Z,C2), C = C1 * C2.",
              ["cpath"], ("d",)),
    "lpath": ("lpath(X,Z,max<D>) <- d(X,Z,D).\n"
              "lpath(X,Z,max<D>) <- lpath(X,Y,D1), d(Y,Z,D2), D = D1 + D2.",
              ["lpath"], ("d",)),
}
N = 7  # vertex domain [0, N); small keeps the naive reference fast
ARITY = {"tc": 2, "sg": 2, "p": 2, "q": 2, "dpath": 3, "cpath": 3,
         "lpath": 3}
AGG_POS = {"dpath": 2, "cpath": 2, "lpath": 2}
ADDITIVE_SHAPES = ("cpath",)  # shapes whose fast path runs accumulate form


def gen_case(case: int):
    """Deterministic random (program, db, queries) for one case index."""
    rng = random.Random(1_000_003 * DIFF_SEED + case)
    shape = rng.choice(sorted(SHAPES))
    text, preds, rels = SHAPES[shape]
    db = {}
    # fixed row count: every EDB quantizes to ONE index/scan bucket, so the
    # sweep exercises many programs against few compiled fixpoint shapes
    n_edges = 12
    for rel in rels:
        if rel == "d":
            # acyclic weighted arcs (src < dst); duplicates stay in — set
            # semantics must collapse them identically on every path
            rows = []
            for _ in range(n_edges):
                a, b = sorted(rng.sample(range(N), 2))
                rows.append([a, b, rng.randint(1, 3)])
        elif rel == "w":
            rows = [[rng.randrange(N), rng.randrange(N), rng.randint(1, 6)]
                    for _ in range(n_edges)]
        else:
            rows = [[rng.randrange(N), rng.randrange(N)]
                    for _ in range(n_edges)]
        db[rel] = np.asarray(rows, np.int64)
    queries = [gen_query(rng, rng.choice(preds)) for _ in range(rng.randint(4, 7))]
    return shape, text, db, queries


def gen_query(rng, pred: str) -> Literal:
    """Random goal: constants, free vars and *repeated* vars at any position
    (the aggregate value position keeps a lower constant rate — fully
    exercising residual filters without starving the interesting shapes)."""
    names = ["X", "Y", "Z"]
    args = []
    for i in range(ARITY[pred]):
        p_const = 0.2 if i == AGG_POS.get(pred) else 0.45
        if rng.random() < p_const:
            args.append(Const(rng.randrange(N + 1)))  # may miss the domain
        else:
            args.append(Var(rng.choice(names)))  # collisions = repeated vars
    return Literal(pred, tuple(args))


def as_set(res) -> set:
    """Engine/service answer -> set of full literal-position tuples."""
    if isinstance(res, tuple):
        rows, vals = res
        return {(*map(int, r[:2]), int(v)) for r, v in zip(rows, vals)}
    return {tuple(map(int, r)) for r in res}


def check(path: str, case, q, got, want):
    assert as_set(got) == want, (
        f"path={path} case={case} query={q!r}: "
        f"missing={sorted(want - as_set(got))[:4]} "
        f"extra={sorted(as_set(got) - want)[:4]}")


CAPS = dict(default_cap=4096)

COUNTERS = {"launches": None, "rows": "rows", "pad_rows": "pad",
            "iterations": "iterations", "active_rows": "active_rows",
            "delta_facts": "delta_facts"}


def check_fixpoint_accounting(case, svc, ref, queries):
    """``explain()``'s fixpoint totals equal the sums over the flushes'
    ``device_sync`` spans.  On an idempotent carrier the seed rows (each
    launched source's out-arcs) plus the Δ entries cover the reference
    model's facts for those sources: exactly on the bool carrier, where an
    entry changes once; at least on min/max-plus, where Δ also counts
    improvements."""
    syncs = [e["args"] for e in svc.tracer.spans("device_sync")]
    for pred, rel in svc.explain()["relations"].items():
        fx, mine = rel["fixpoint"], [a for a in syncs if a["pred"] == pred]
        for key, arg in COUNTERS.items():
            want = len(mine) if arg is None else sum(a[arg] for a in mine)
            assert fx[key] == want, (case, pred, key, fx)
        low = svc._lowering(pred)
        if low.kind == "plustimes" or not fx["launches"]:
            continue
        srcs = {svc._dense_source(q) for q in queries if q.pred == pred}
        seed = len({(int(r[0]), int(r[1])) for r in svc.db[low.edb]
                    if int(r[0]) in srcs})
        final = len({t[:2] for t in ref.get(pred, ()) if t[0] in srcs})
        if low.kind == "bool":
            assert seed + fx["delta_facts"] == final, (case, pred, fx)
        else:
            assert seed + fx["delta_facts"] >= final > 0 or final == 0, \
                (case, pred, fx)


@pytest.mark.parametrize("case", range(DIFF_CASES))
def test_differential(case):
    shape, text, db, queries = gen_case(case)
    ref = ref_model(text, db)
    want = {i: ref_answer(ref, q) for i, q in enumerate(queries)}

    # 1. naive full model (+ goal filter through the reference's own filter)
    eng = Engine(text, db=db, **CAPS).run()
    for pred in SHAPES[shape][1]:
        info = eng._pred_info[pred]
        got = eng.query_agg(pred) if info.is_agg else eng.query(pred)
        assert as_set(got) == ref.get(pred, set()), (shape, case, pred)

    # 2. magic ask / 3. demanded-strata fallback
    eng_m = Engine(text, db=db, **CAPS)
    eng_d = Engine(text, db=db, magic=False, **CAPS)
    for i, q in enumerate(queries):
        check("magic", case, q, eng_m.ask(q), want[i])
        check("demand", case, q, eng_d.ask(q), want[i])

    # engine-level qid batch (one fixpoint per same-shape group): every 4th
    # case — the service path below exercises the same rewrite with bucketed
    # seeds; this samples the inline-seed variant without re-tracing per B
    if case % 4 == 0:
        for i, got in enumerate(eng_m.ask_batch(queries)):
            check("engine-batch", case, queries[i], got, want[i])

    # 4./5. service batched then cached (second round = pure cache hits)
    svc = DatalogService(text, db=db, **CAPS)
    dense_res = svc.ask_batch(queries)
    for i, got in enumerate(dense_res):
        check("service-batch", case, queries[i], got, want[i])
    h0 = svc.cache.hits
    for i, got in enumerate(svc.ask_batch(queries)):
        check("service-cached", case, queries[i], got, want[i])
    assert svc.cache.hits > h0

    # 7. CSR-forced serving: the sparse frontier engine must agree with the
    # oracle AND be bit-identical to the dense service's formatted answers
    svc_csr = DatalogService(text, db=db, sparse=True, **CAPS)
    for i, got in enumerate(svc_csr.ask_batch(queries)):
        check("service-csr", case, queries[i], got, want[i])
        d = dense_res[i]
        for a, b in zip(d if isinstance(d, tuple) else (d,),
                        got if isinstance(got, tuple) else (got,)):
            assert np.array_equal(a, b), \
                f"case={case} query={queries[i]!r}: dense/CSR not bit-identical"

    # 11. counting fast path: additive shapes' single-source closures (the
    # dense accumulate fixpoint AND its CSR twin) against the graph-level
    # path-count oracle — exact integer comparison, no fp tolerance.  The
    # oracle sums Π-of-weights over distinct paths, which is exactly the
    # Datalog sum-aggregate fixpoint on the deduped arc set.
    if shape in ADDITIVE_SHAPES:
        arcs = np.unique(db["d"], axis=0)  # set semantics, like every path
        for svc_c, name in ((svc, "service-counting"),
                            (svc_csr, "service-counting-csr")):
            for s in range(N):
                counts = ref_path_counts(arcs, s)
                rows, vals = svc_c.ask(SHAPES[shape][1][0], (s, None, None))
                got = {int(r[1]): int(v) for r, v in zip(rows, vals)}
                assert got == counts, (
                    f"path={name} case={case} src={s}: got {got} "
                    f"want {counts}")

    # 10. tuned-kernel serving: a pinned KernelConfig (no measurement) forces
    # the sliced-ELL layout + Pallas tile-skip kernels on every CSR relation;
    # answers must stay bit-identical to the dense service
    from repro.kernels.autotune import KernelConfig
    svc_tuned = DatalogService(text, db=db, sparse=True,
                               tune=KernelConfig(use_kernel=True), **CAPS)
    for i, got in enumerate(svc_tuned.ask_batch(queries)):
        check("service-tuned", case, queries[i], got, want[i])
        d = dense_res[i]
        for a, b in zip(d if isinstance(d, tuple) else (d,),
                        got if isinstance(got, tuple) else (got,)):
            assert np.array_equal(a, b), \
                f"case={case} query={queries[i]!r}: tuned not bit-identical"

    # 8. async admission front-end: the same queries submitted concurrently
    # from two threads; arrival timing makes the dispatcher's flush
    # composition nondeterministic, so answers are compared as sets — the
    # invariant under test is that coalescing never changes an answer
    front = AsyncDatalogService(DatalogService(text, db=db, **CAPS),
                                max_wait_ms=1.0, max_batch=4)
    futs: list = [None] * len(queries)

    def _submit(lo, hi):
        for i in range(lo, hi):
            futs[i] = front.submit(queries[i])

    half = len(queries) // 2
    workers = [threading.Thread(target=_submit, args=(0, half)),
               threading.Thread(target=_submit, args=(half, len(queries)))]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    for i, f in enumerate(futs):
        check("service-async", case, queries[i], f.result(timeout=120),
              want[i])
    front.close()

    # 9. observed serving: tracing on must not perturb answers — bit-
    # identical to the plain dense service — the loops' counters must
    # account for every Δ, and warm re-serving must be launch- and
    # retrace-free
    from repro.core.engine import fixpoint_trace_count
    svc_obs = DatalogService(text, db=db, tracer=True, **CAPS)
    for i, got in enumerate(svc_obs.ask_batch(queries)):
        check("service-observed", case, queries[i], got, want[i])
        d = dense_res[i]
        for a, b in zip(d if isinstance(d, tuple) else (d,),
                        got if isinstance(got, tuple) else (got,)):
            assert np.array_equal(a, b), \
                f"case={case} query={queries[i]!r}: observed not bit-identical"
    check_fixpoint_accounting(case, svc_obs, ref, queries)
    tc0 = fixpoint_trace_count()
    totals = svc_obs.explain()["relations"]
    for i, got in enumerate(svc_obs.ask_batch(queries)):  # warm batch
        check("service-observed-warm", case, queries[i], got, want[i])
    assert fixpoint_trace_count() == tc0, \
        f"case={case}: warm observed batch retraced a fixpoint"
    assert svc_obs.explain()["relations"] == totals, \
        f"case={case}: warm observed batch launched a fixpoint"

    # 6. append-resume: serve a prefix EDB, append the tail, re-serve
    rel = SHAPES[shape][2][0]
    k = 1 + case % 3
    if len(db[rel]) > k:
        base = dict(db)
        base[rel] = db[rel][:-k]
        svc2 = DatalogService(text, db=base, **CAPS)
        svc2.ask_batch(queries)  # populate caches + template snapshots
        svc2.append(rel, db[rel][-k:])
        for i, got in enumerate(svc2.ask_batch(queries)):
            check("append-resume", case, queries[i], got, want[i])
        # CSR twin: resume the packed-arc closures (COO-tail append path)
        svc3 = DatalogService(text, db=base, sparse=True, **CAPS)
        svc3.ask_batch(queries)
        svc3.append(rel, db[rel][-k:])
        for i, got in enumerate(svc3.ask_batch(queries)):
            check("append-resume-csr", case, queries[i], got, want[i])

        # 12. durable serving: kill/restart between batches — snapshot + WAL
        # recovery must serve answers bit-identical to the never-restarted
        # twin (svc2 above, same EDB prefix + append stream).  Odd cases
        # crash with NO snapshot (pure WAL replay from genesis); every third
        # case re-appends the exact same rows pre-crash, so recovery replays
        # duplicate WAL records — a no-op under set semantics.
        with tempfile.TemporaryDirectory() as dur_dir:
            svc_d = DatalogService(text, db=base, durable_dir=dur_dir,
                                   **CAPS)
            svc_d.ask_batch(queries)
            if case % 2 == 0:
                svc_d.snapshot(wait=True)
            svc_d.append(rel, db[rel][-k:])
            twin_epoch = svc2.epoch
            if case % 3 == 0:
                svc_d.append(rel, db[rel][-k:])  # duplicate append
                svc2.append(rel, db[rel][-k:])
                twin_epoch = svc2.epoch
            twin_res = svc2.ask_batch(queries)
            del svc_d  # crash: no close(), no final snapshot
            svc_r = DatalogService(text, db=base, durable_dir=dur_dir,
                                   **CAPS)
            rep = svc_r.explain()["durability"]["recovery"]
            assert rep["mode"] == ("warm" if case % 2 == 0 else "cold"), rep
            assert svc_r.epoch == twin_epoch, (case, rep)
            for i, got in enumerate(svc_r.ask_batch(queries)):
                check("service-durable", case, queries[i], got, want[i])
                t = twin_res[i]
                for a, b in zip(t if isinstance(t, tuple) else (t,),
                                got if isinstance(got, tuple) else (got,)):
                    assert np.array_equal(a, b), (
                        f"case={case} query={queries[i]!r}: durable restart "
                        "not bit-identical to the no-restart twin")
            svc_r.close()


# -- hypothesis variant (runs when hypothesis is installed) ------------------

if HAVE_HYPOTHESIS:
    edge_lists = st.lists(
        st.tuples(st.integers(0, N - 1), st.integers(0, N - 1)),
        min_size=5, max_size=12)
else:  # stub: @given turns this into a skip
    edge_lists = st.lists(st.tuples())


@given(edge_lists, st.integers(0, N), st.integers(0, N))
@settings(max_examples=20, deadline=None)
def test_property_tc_all_paths(edge_list, a, b):
    """Hypothesis-driven twin of the deterministic sweep (TC only): shrunk
    counterexamples beat case indexes when this one trips."""
    db = {"e": np.asarray(edge_list, np.int64)}
    text = SHAPES["tc"][0]
    ref = ref_model(text, db)
    queries = [Literal("tc", (Const(a), Var("Y"))),
               Literal("tc", (Var("X"), Const(b))),
               Literal("tc", (Const(a), Const(b))),
               Literal("tc", (Var("X"), Var("X")))]
    eng = Engine(text, db=db, **CAPS)
    svc = DatalogService(text, db=db, **CAPS)
    batched = svc.ask_batch(queries)
    for q, got in zip(queries, eng.ask_batch(queries)):
        assert as_set(got) == ref_answer(ref, q), q
    for q, got in zip(queries, batched):
        assert as_set(got) == ref_answer(ref, q), q
