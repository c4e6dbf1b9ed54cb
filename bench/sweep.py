#!/usr/bin/env python3
"""Sweeps that size a cell, on the chip, in one process per call:

    python bench/sweep.py --workload <cell> --seed <n> [--scale S]
        [--pads] [--rates r1,r2,...] [--seconds 20]

``--scale`` serves the cell's configuration at another scale (``bits``
follows it).  ``--pads`` times one flush of distinct uniform sources at
each batch pad, with its fixpoint iterations.  ``--rates`` offers the
cell's mix at each rate in turn, each for ``--seconds`` after its
pre-roll, and reports the window's answers per second, latency quantiles,
mean flush and the backlog's growth: how much later the last quarter of
the window's queries were answered than the first quarter.  The knee is
the highest rate at which the backlog does not grow.  One JSON line per
measurement.  Needs a TPU, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def emit(**kv) -> None:
    print(json.dumps(kv), flush=True)


def time_pads(svc, cfg, graph, seed) -> None:
    import numpy as np
    from bench.lib import graph500, harness

    cand = np.flatnonzero(graph.out_degree > 0)
    rng = graph500.rng_for(seed, 99)
    for pad in svc.batch_pads:
        srcs = rng.choice(cand, pad, replace=False)
        t = time.perf_counter()
        pending = svc.launch_batch([harness.query(cfg, int(s)) for s in srcs])
        answers = svc.finalize_batch(pending)
        dt = time.perf_counter() - t
        its = int(pending.dense[0][5].iterations)
        sizes = [len(harness.answer_vertices(a)) for a in answers]
        emit(kind="pad", scale=cfg["scale"], pad=pad, seconds=dt,
             iterations=its, ms_per_query=dt / pad * 1e3,
             mean_answer=float(np.mean(sizes)))
        svc.cache.clear()


def knee(front, cell, graph, seed, rates, seconds) -> None:
    import numpy as np
    from bench.lib import harness, traffic

    cfg = cell.config
    for rate in rates:
        mix = copy.deepcopy(cell.mix)
        mix["arrivals"]["rate_qps"] = rate
        due, srcs = traffic.schedule(mix, seconds, graph, seed)
        before = dict(vars(front.stats))
        t0 = time.perf_counter() + mix["preroll_s"] + 0.05
        t1 = t0 + seconds
        pending = harness.Outstanding()
        recs = harness.drive(front, cfg, due, srcs, t0, set(), 0, pending)
        pending.wait(t1 + mix["drain_s"] - time.perf_counter())
        t_end = time.perf_counter()
        win = [r for r in recs if t0 <= r.due < t1]
        lat = [(r.done - r.due) if r.ok else t_end - r.due for r in win]
        q = max(len(lat) // 4, 1)
        after = vars(front.stats)
        flushes = after["flushes"] - before["flushes"]
        emit(kind="rate", scale=cfg["scale"], rate=rate, seconds=seconds,
             due=len(win),
             answers_per_s=sum(1 for r in recs
                               if r.ok and t0 <= r.done <= t1) / seconds,
             p50=float(np.percentile(lat, 50)),
             p95=float(np.percentile(lat, 95)),
             backlog_growth_s=float(np.median(lat[-q:]) - np.median(lat[:q])),
             unanswered=sum(not r.ok for r in win),
             flushes=flushes,
             mean_flush=(after["flushed_queries"] - before["flushed_queries"])
             / max(flushes, 1),
             short_circuits=after["short_circuits"] - before["short_circuits"])
        front.svc.cache.clear()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--scale", type=int)
    ap.add_argument("--pads", action="store_true")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench.lib import graph500, harness, spec

    cell = spec.load(ROOT, args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    from repro import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    enable_compile_cache()
    cfg = cell.config
    if args.scale:
        cfg.update(scale=args.scale, bits=max(args.scale, cfg["bits"]))
    graph = graph500.generate(cfg, args.seed)
    t_graph = time.perf_counter() - T_START
    front = harness.build(cfg, graph)
    svc = front.svc
    t_built = time.perf_counter() - T_START
    harness.warm_up(svc, cfg, graph)
    r = svc.explain()["relations"][cfg["query"]]
    emit(kind="setup", scale=cfg["scale"], arcs=len(graph.arcs),
         n_alloc=r.get("n_alloc"), e_alloc=r.get("e_alloc"),
         graph_s=t_graph, built_s=t_built,
         setup_s=time.perf_counter() - T_START,
         memory=devices[0].memory_stats().get("bytes_in_use"))
    try:
        if args.pads:
            time_pads(svc, cfg, graph, args.seed)
        if args.rates:
            knee(front, cell, graph, args.seed,
                 [float(x) for x in args.rates.split(",")], args.seconds)
    finally:
        front.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
