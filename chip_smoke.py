#!/usr/bin/env python3
"""Chip smoke test: serve REACH and SSSP through ``AsyncDatalogService`` on a
TPU, at 2^20 vertices, and check the answers against scipy.

    python3 chip_smoke.py [--seed S]          # one chip
    python3 chip_smoke.py --chips 4 [--seed S]  # the 4-chip mesh path only

The deployment: ``powerlaw_graph(n=2**20, m=2**24, seed)`` (Zipf in-degrees;
|E| after dedup is printed), with integer SSSP weights drawn uniformly from
[1, 64]; one program holding a TC-shaped REACH rule and the ``min<>`` SSSP
rule, served by one ``DatalogService(bits=20)`` behind an
``AsyncDatalogService``.  Traffic: two flushes of 32 single-source REACH
queries and two of 32 SSSP queries with Zipf-skewed sources, then an append
of 1,024 seeded arcs (into ``arc``, and weighted into ``warc``), then one
flush of 8 + 8 queries.  For 4 sampled sources per carrier, before and after
the append, every answer must equal ``scipy.sparse.csgraph``.

The admission ``max_batch`` is the widest batch pad whose fixpoints the
device's free memory admits, by ``compiled.memory_analysis()``.  Lines that
start with ``smoke:`` are smoke output, not benchmark metrics.  The last
line is one JSON object naming the device.  The script exits non-zero, and
prints no result, when JAX finds no TPU or any phase fails.

``--chips 4`` runs only the data-parallel path (``DatalogService(mesh=)``):
one flush of 32 REACH and one of 32 SSSP on a 4-device mesh, the same
queries on one chip in the same process, and requires equal answers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

PROGRAM = """
reach(X,Y) <- arc(X,Y).
reach(X,Y) <- reach(X,Z), arc(Z,Y).
dpath(X,Z,min<D>) <- warc(X,Z,D).
dpath(X,Z,min<D>) <- dpath(X,Y,Dxy), warc(Y,Z,Dyz), D = Dxy + Dyz.
"""
CARRIERS = ("reach", "dpath")
N_VERTICES = 1 << 20
N_ARCS = 1 << 24
MAX_WEIGHT = 64
ZIPF_A = 1.2  # source skew: a few hot sources, a long tail
#: batch widths the max_batch probe tries, widest last; past 256 the probe's
#: compiles alone would outgrow the smoke's time budget
BATCH_LADDER = (32, 64, 128, 256)
INFLIGHT = 2  # AsyncDatalogService's launched-but-unfinalized batches


def log(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def make_deployment(n: int, m: int, seed: int):
    """The graph, its weights and the appended arcs, all from ``seed``."""
    import numpy as np
    from repro.data.graphs import powerlaw_graph

    arcs = powerlaw_graph(n=n, m=m, seed=seed)
    rng = np.random.default_rng(seed + 1)
    warcs = np.concatenate(
        [arcs, rng.integers(1, MAX_WEIGHT + 1, (len(arcs), 1))], axis=1)
    new = rng.integers(0, n, (1024, 2))
    new = new[new[:, 0] != new[:, 1]]
    new_w = np.concatenate(
        [new, rng.integers(1, MAX_WEIGHT + 1, (len(new), 1))], axis=1)
    return arcs, warcs, new, new_w


def zipf_sources(rng, n: int, k: int, perm) -> list[int]:
    """``k`` sources, Zipf(ZIPF_A) over vertex ranks shuffled by ``perm``."""
    import numpy as np
    ranks = np.minimum(rng.zipf(ZIPF_A, k), n) - 1
    return [int(s) for s in perm[ranks]]


class Reference:
    """scipy.sparse.csgraph answers for single-source REACH / SSSP, with the
    program's semantics: paths of at least one arc, so a source is in its
    own answer only on a cycle; parallel arcs keep their lightest weight."""

    def __init__(self, n: int, arcs, warcs):
        import numpy as np
        from scipy.sparse import csr_matrix
        self.n = n
        ones = np.ones(len(arcs), np.float64)
        self.adj = csr_matrix((ones, (arcs[:, 0], arcs[:, 1])), shape=(n, n))
        self.adj_t = self.adj.T.tocsr()
        order = np.lexsort((warcs[:, 2], warcs[:, 1], warcs[:, 0]))
        w = warcs[order]
        first = np.ones(len(w), bool)
        first[1:] = (w[1:, 0] != w[:-1, 0]) | (w[1:, 1] != w[:-1, 1])
        w = w[first]
        self.w = csr_matrix((w[:, 2].astype(np.float64), (w[:, 0], w[:, 1])),
                            shape=(n, n))
        self.w_t = self.w.T.tocsr()

    def reach(self, s: int):
        import numpy as np
        from scipy.sparse.csgraph import breadth_first_order
        seen = breadth_first_order(self.adj, s, directed=True,
                                   return_predecessors=False)
        out = seen[seen != s]
        if np.isin(self.adj_t[s].indices, seen).any():  # back on a cycle
            out = np.append(out, s)
        return np.sort(out)

    def dpath(self, s: int):
        import numpy as np
        from scipy.sparse.csgraph import dijkstra
        dist = dijkstra(self.w, directed=True, indices=s)
        back = self.w_t[s]
        loop = dist[back.indices] + back.data
        dist[s] = loop.min() if len(loop) else np.inf
        dst = np.nonzero(np.isfinite(dist))[0]
        return dst, dist[dst]


def check(ref: Reference, pred: str, s: int, got) -> None:
    """Raise unless the served answer for source ``s`` equals scipy's."""
    import numpy as np
    if pred == "reach":
        want = ref.reach(s)
        have = np.sort(np.asarray(got)[:, 1])
        if not np.array_equal(have, want):
            raise AssertionError(
                f"reach({s}, Y): served {len(have)} rows, scipy {len(want)}")
        return
    rows, vals = got
    order = np.argsort(rows[:, 1])
    dst, dist = ref.dpath(s)
    if not (np.array_equal(rows[order, 1], dst)
            and np.array_equal(vals[order], dist.astype(np.int64))):
        raise AssertionError(
            f"dpath({s}, Z, D): served {len(rows)} rows differ from scipy's "
            f"{len(dst)}")


def choose_max_batch(svc, free_bytes, ladder=BATCH_LADDER):
    """The widest ladder width whose fixpoints fit ``free_bytes()``, read
    once the first width's compiles have built both relations.

    A width the compiler refuses as over the HBM does not fit.  Otherwise
    it fits when the largest one-launch need over the carriers
    (``memory_analysis``: temporaries + output + the (B, n) seed argument)
    plus the answer tables that in-flight batches keep alive
    (``INFLIGHT + 2`` per carrier: the queued launches, the one being
    finalized and the one being launched) is at most the free bytes.
    Returns (width, free bytes, per-width report, compile seconds)."""
    import jax

    report, best, secs, free = [], None, 0.0, None
    for b in ladder:
        needs, tables, refused = {}, 0, None
        for pred in CARRIERS:
            t0 = time.perf_counter()
            try:
                compiled = svc.compile_batch(pred, b)
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                # the compiler itself found the program over the HBM
                refused = f"{pred}: {str(e).splitlines()[0]}"
                break
            finally:
                secs += time.perf_counter() - t0
            ma = compiled.memory_analysis()
            rel = svc.explain()["relations"][pred]
            table = b * rel["n_alloc"] * (1 if rel["semiring"] == "bool"
                                          else 4)
            needs[pred] = ma.temp_size_in_bytes + ma.output_size_in_bytes \
                + table
            tables += table
        if free is None:
            free = free_bytes()
        if refused:
            report.append({"batch": b, "fits": False, "compiler": refused})
            break
        need = max(needs.values()) + (INFLIGHT + 2) * tables
        fits = need <= free
        report.append({"batch": b, "need_bytes": need, "fits": fits,
                       **{f"{p}_launch_bytes": v for p, v in needs.items()}})
        if not fits:
            break
        best = b
    if best is None:
        raise RuntimeError(f"no batch width fits {free} free bytes: "
                           f"{report}")
    return best, free, report, secs


def serve_and_check(n: int = N_VERTICES, m: int = N_ARCS, seed: int = 0,
                    free_bytes: int | None = None) -> dict:
    """The one-chip smoke: build, size, serve, append, check.  Returns the
    summary; raises on any mismatch.  ``free_bytes`` stands in for the
    device's free memory where the backend reports none (CPU rehearsal)."""
    import jax
    import numpy as np
    from repro.service import AsyncDatalogService, DatalogService

    t_setup = time.perf_counter()
    arcs, warcs, new, new_w = make_deployment(n, m, seed)
    log(f"graph: {n} vertices, |E| = {len(arcs)} arcs after dedup "
        f"(powerlaw_graph n={n} m={m} seed={seed}); append of {len(new)} arcs")
    dev = jax.devices()[0]
    svc = DatalogService(PROGRAM, db={"arc": arcs, "warc": warcs}, bits=20,
                         batch_pads=(8,) + BATCH_LADDER)
    log(f"service built in {time.perf_counter() - t_setup} s (data, engine)")

    def device_free() -> int:
        rels = svc.explain()["relations"]
        for pred in CARRIERS:
            r = rels[pred]
            log(f"{pred}: repr={r['repr']} n_alloc={r['n_alloc']} "
                f"nnz={r['nnz']} e_alloc={r['e_alloc']} slices="
                f"{[(s['rows'], s['cap']) for s in r['padding']['slices']]}")
        stats = dev.memory_stats()
        if stats is None:
            return free_bytes
        log(f"device bytes in use with both relations loaded: "
            f"{stats['bytes_in_use']} of {stats['bytes_limit']}")
        return stats["bytes_limit"] - stats["bytes_in_use"]

    max_batch, free, probe, compile_s = choose_max_batch(svc, device_free)
    for row in probe:
        log(f"max_batch probe: {json.dumps(row)}")
    # the front end makes max_batch the service's widest pad: no flush or
    # append resume launches wider
    front = AsyncDatalogService(svc, max_wait_ms=50.0, max_batch=max_batch,
                                inflight=INFLIGHT)
    log(f"max_batch = {max_batch} (free bytes {free}); batch pads "
        f"{svc.batch_pads}; fixpoint compile seconds {compile_s}; setup "
        f"seconds {time.perf_counter() - t_setup}")

    ref = Reference(n, arcs, warcs)
    rng = np.random.default_rng(seed + 2)
    perm = rng.permutation(n)
    checked = 0
    try:
        t0 = time.perf_counter()
        first = None
        served: dict = {p: {} for p in CARRIERS}
        for pred in CARRIERS:
            for _ in range(2):
                srcs = zipf_sources(rng, n, 32, perm)
                futs = [(s, front.submit(_query(pred, s))) for s in srcs]
                for s, f in futs:
                    served[pred][s] = f.result(timeout=900)
                    if first is None:
                        first = time.perf_counter() - t0
        log(f"time to first answer: {first} s; 128 queries answered in "
            f"{time.perf_counter() - t0} s")
        for pred in CARRIERS:
            for s in _sample(rng, served[pred]):
                check(ref, pred, s, served[pred][s])
                checked += 1
        log(f"answers checked before append: {checked}, all equal to scipy")

        front.append("arc", new)
        front.append("warc", new_w)
        ref = Reference(n, np.concatenate([arcs, new]),
                        np.concatenate([warcs, new_w]))
        after: dict = {p: {} for p in CARRIERS}
        futs = [(p, s, front.submit(_query(p, s))) for p in CARRIERS
                for s in zipf_sources(rng, n, 8, perm)]
        for p, s, f in futs:
            after[p][s] = f.result(timeout=900)
        for pred in CARRIERS:
            for s in _sample(rng, after[pred]):
                check(ref, pred, s, after[pred][s])
                checked += 1
        log(f"answers checked after append: {checked} in all, all equal "
            "to scipy")
        rep = front.explain()
    finally:
        front.close()
    adm = rep["admission"]["counters"]
    log(f"admission: {adm['flushes']} flushes, {adm['flushed_queries']} "
        f"flushed queries, {adm['short_circuits']} cache short-circuits, "
        f"{adm['failed_flushes']} failed; resumed closures "
        f"{rep['service']['resumed_rows']}")
    if adm["failed_flushes"]:
        raise RuntimeError(f"{adm['failed_flushes']} flushes failed")
    stats = dev.memory_stats()
    if stats is not None:
        log(f"device peak bytes in use: {stats['peak_bytes_in_use']}")
    return {"arcs": int(len(arcs)), "max_batch": max_batch,
            "compile_seconds": compile_s, "first_answer_seconds": first,
            "checked": checked}


def _query(pred: str, s: int):
    return (pred, (s, None)) if pred == "reach" else (pred, (s, None, None))


def _sample(rng, answers: dict, k: int = 4) -> list[int]:
    keys = sorted(answers)
    return [keys[i] for i in rng.choice(len(keys), min(k, len(keys)),
                                        replace=False)]


def mesh_and_compare(n: int = N_VERTICES, m: int = N_ARCS, seed: int = 0,
                     chips: int = 4) -> dict:
    """The data-parallel path: batch rows sharded over a ``chips``-device
    mesh (the closed frontier's shards must sit on distinct devices), the
    same queries on one device, equal answers required."""
    import jax
    import numpy as np
    from repro.service import AsyncDatalogService, DatalogService

    arcs, warcs, _, _ = make_deployment(n, m, seed)
    log(f"graph: {n} vertices, |E| = {len(arcs)} arcs after dedup "
        f"(powerlaw_graph n={n} m={m} seed={seed})")
    mesh = jax.make_mesh((chips,), ("data",),
                         devices=jax.devices()[:chips])
    rng = np.random.default_rng(seed + 2)
    perm = rng.permutation(n)
    queries = [_query(p, s) for p in CARRIERS
               for s in zipf_sources(rng, n, 32, perm)]
    answers = []
    for label, kw in (("mesh", {"mesh": mesh}), ("one chip", {})):
        svc = DatalogService(PROGRAM, db={"arc": arcs, "warc": warcs},
                             bits=20, batch_pads=(8, 32), **kw)
        front = AsyncDatalogService(svc, max_wait_ms=50.0, max_batch=32)
        t0 = time.perf_counter()
        try:
            got = []
            for pred in CARRIERS:  # one flush per carrier
                futs = [front.submit(q) for q in queries if q[0] == pred]
                got += [f.result(timeout=900) for f in futs]
            flushes = front.explain()["admission"]["counters"]["flushes"]
        finally:
            front.close()
        log(f"{label}: {len(got)} queries in {flushes} flushes, "
            f"{time.perf_counter() - t0} s")
        if kw:
            # relaunch the REACH batch (cache cleared) and look at its
            # closed frontier before finalize gathers it to the host
            svc.cache.clear()
            pending = svc.launch_batch([q for q in queries if q[0] == "reach"])
            table = pending.dense[0][5].table
            devs = sorted({str(s.device) for s in table.addressable_shards})
            svc.finalize_batch(pending)
            log(f"mesh: closed frontier {tuple(table.shape)} in "
                f"{len(devs)} shards on devices {devs}")
            if len(devs) != chips:
                raise AssertionError(f"frontier sits on {len(devs)} devices, "
                                     f"not {chips}")
        answers.append(got)
    for q, a, b in zip(queries, *answers):
        same = (np.array_equal(a, b) if q[0] == "reach" else
                all(np.array_equal(x, y) for x, y in zip(a, b)))
        if not same:
            raise AssertionError(f"{q}: mesh and one-chip answers differ")
    log(f"answers checked: {len(queries)} mesh answers equal the one-chip "
        "answers")
    return {"arcs": int(len(arcs)), "checked": len(queries)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh path and its one-chip twin")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{platform!r} ({len(devices)} {devices[0].device_kind} "
              "device(s))", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2

    from repro import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    if args.chips == 1:
        serve_and_check(seed=args.seed)
    else:
        mesh_and_compare(seed=args.seed, chips=args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
