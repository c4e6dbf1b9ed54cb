"""One run of one cell: set-up, an open-loop window through
``AsyncDatalogService.submit``, a bounded drain, the check against the
plain reference, and the result line.

Set-up (``setup_s``) runs from the start of the process to the start of the
pre-roll: JAX's start and compile cache, the graph from the seed, the
service build, and one warm-up flush of sink sources (vertices with no
out-arcs, which close in one iteration) at each batch pad, both full and
one short of full, so that every program the window runs is compiled or
loaded from the cache before it starts.  The result cache is cleared after.

Latency is timed from each query's due time to its future resolving.  A
query due in the window that is shed, fails, or is unanswered when the
drain ends is a miss: it counts in ``failed``, and its latency is the time
from its due time to the end of the drain, the least it could have taken.
"""
from __future__ import annotations

import dataclasses
import logging
import math
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

from . import graph500, spec, traffic, work, xtrace
from .reference import Reference

#: the service's spans that the breakdown may name an idle gap after
HOST_SPANS = {"coalesce", "launch_batch", "fixpoint", "device_sync",
              "finalize_batch", "cache_fill", "tuple_split", "append"}
#: JAX's monitoring events of a new program: lowered, and compiled or
#: loaded from the persistent cache
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class CompileCounter(logging.Handler):
    """Counts JAX lowerings and backend compiles while registered, and
    keeps the names JAX logs for them (``jax_log_compiles``), so that a
    compile inside the window says what it was."""

    def __init__(self):
        import jax
        import jax.monitoring
        super().__init__(logging.WARNING)
        self.count = 0
        self.names: list = []
        self._log_compiles = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax").addHandler(self)
        self._listener = self._on  # the same object to unregister
        jax.monitoring.register_event_duration_secs_listener(self._listener)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in COMPILE_EVENTS:
            self.count += 1

    def emit(self, record) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling"):
            self.names.append(msg.split(" with ")[0][len("Compiling "):])

    def close(self) -> None:  # again at exit, from logging.shutdown
        import jax
        import jax.monitoring
        if self._listener is None:
            return
        jax.monitoring.unregister_event_duration_listener(self._listener)
        self._listener = None
        logging.getLogger("jax").removeHandler(self)
        jax.config.update("jax_log_compiles", self._log_compiles)
        super().close()


def profiled_tracer():
    """A service tracer that mirrors every span onto the profiler's clock
    (``jax.profiler.TraceAnnotation``), so the trace reduction can name an
    idle gap after the host span that covers it."""
    import jax
    from repro.obs.trace import Span, Tracer

    class ProfiledSpan(Span):
        __slots__ = ("_ann",)

        def __init__(self, tracer, name, cat, args):
            super().__init__(tracer, name, cat, args)
            self._ann = jax.profiler.TraceAnnotation(name)
            self._ann.__enter__()

        def end(self) -> None:
            if not self._done:
                self._ann.__exit__(None, None, None)
            super().end()

    class ProfiledTracer(Tracer):
        def span(self, name, cat="service", **args):
            return ProfiledSpan(self, name, cat, args or None)

    return ProfiledTracer()


class FlushLog:
    """Records, per launched flush, the sources it sent into the fixpoint
    and how many of its queries the result cache answered at launch."""

    def __init__(self, svc):
        self.flushes: list = []
        launch = svc.launch_batch

        def logged(queries):
            pending = launch(queries)
            # each dense group is (pred, relation, items, uniq, in_range,
            # result, meta): in_range are the sources launched
            self.flushes.append(
                ([s for d in pending.dense for s in d[4]], len(pending.hits)))
            return pending

        svc.launch_batch = logged


def query(cfg: dict, src: int):
    return (cfg["query"], (src,) + (None,) * (cfg["arity"] - 1))


def answer_vertices(answer) -> np.ndarray:
    rows = answer[0] if isinstance(answer, tuple) else answer
    return np.asarray(rows)[:, 1]


def build(cfg: dict, graph: graph500.Graph, tracer=None):
    """The served deployment: one ``DatalogService`` over the graph behind
    an ``AsyncDatalogService`` sized by the configuration."""
    from repro.service import AsyncDatalogService, DatalogService

    svc = DatalogService(cfg["program"], db={cfg["relation"]: graph.arcs},
                         bits=cfg["bits"], batch_pads=tuple(cfg["batch_pads"]),
                         sparse=True, result_cache=cfg["result_cache"],
                         tracer=tracer)
    return AsyncDatalogService(svc, max_wait_ms=cfg["max_wait_ms"],
                               max_batch=cfg["max_batch"],
                               queue_depth=cfg["queue_depth"],
                               inflight=cfg["inflight"])


def warm_up(svc, cfg: dict, graph: graph500.Graph) -> None:
    sinks = graph500.sinks(graph)
    for pad in svc.batch_pads:
        for b in sorted({max(pad - 1, 1), pad}):
            if b > len(sinks):
                raise ValueError(f"{len(sinks)} sinks cannot warm a flush "
                                 f"of {b}")
            svc.cache.clear()  # a cached sink would shrink the flush
            svc.ask_batch([query(cfg, int(s)) for s in sinks[:b]])
    svc.cache.clear()


class Outstanding:
    """Submitted queries whose futures have not resolved yet."""

    def __init__(self):
        self._cv = threading.Condition()
        self.count = 0

    def add(self) -> None:
        with self._cv:
            self.count += 1

    def done(self) -> None:
        with self._cv:
            self.count -= 1
            self._cv.notify_all()

    def wait(self, timeout: float) -> None:
        with self._cv:
            self._cv.wait_for(lambda: self.count == 0, max(timeout, 0.0))


@dataclasses.dataclass
class Rec:
    """One offered query: due and resolve times on the host's clock.  Only
    what the run reads later is kept of its answer: the answer itself for a
    source in the check's sample, and in a traced run the bitmap of the
    vertices it reached, so thousands of answers do not stay in memory."""

    due: float
    src: int
    keep: bool = False
    bitmap_n: int = 0  # vertices, where the reached bitmap is kept
    submitted: float = math.nan
    done: float = math.nan
    shed: bool = False
    ok: bool = False
    answer: object = None
    reached: object = None

    def resolved(self, fut) -> None:
        self.done = time.perf_counter()
        self.ok = fut.exception() is None
        if self.ok:
            got = fut.result()
            if self.keep:
                self.answer = got
            if self.bitmap_n:
                mask = np.zeros(self.bitmap_n, bool)
                mask[answer_vertices(got)] = True
                self.reached = np.packbits(mask)


def drive(front, cfg: dict, due: np.ndarray, srcs: np.ndarray, t0: float,
          sample: set, bitmap_n: int, pending: Outstanding) -> list:
    """Submit each query at ``t0 + due`` (open loop) and return the records."""
    from repro.service import QueueFullError

    recs = []
    for d, s in zip(due.tolist(), srcs.tolist()):
        rec = Rec(due=t0 + d, src=s, keep=s in sample, bitmap_n=bitmap_n)
        wait = rec.due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        rec.submitted = time.perf_counter()
        recs.append(rec)
        try:
            fut = front.submit(query(cfg, s))
        except QueueFullError:
            rec.shed = True
            continue
        pending.add()

        def resolved(f, rec=rec):
            rec.resolved(f)
            pending.done()

        fut.add_done_callback(resolved)
    return recs


def percentile(xs: list, q: float) -> float:
    return float(np.percentile(np.asarray(xs, float), q))


def sample_sources(srcs: np.ndarray, k: int, seed: int) -> set:
    """The check's sample: ``k`` distinct sources of the window's queries,
    drawn from the seed before the window opens."""
    distinct = np.unique(srcs)
    rng = graph500.rng_for(seed, graph500.SAMPLE)
    return set(rng.choice(distinct, min(k, len(distinct)),
                          replace=False).tolist())


def check(cfg: dict, graph: graph500.Graph, answers: dict) -> dict:
    """Compare each of ``answers`` (source -> served answer, or a callable
    that makes it) with the reference.  Returns {"checked",
    "wrong_answers", "worst"}."""
    ref = Reference(graph.n, graph.arcs)
    wrong, worst = 0, None
    for s in sorted(answers):
        got = answers[s]
        why = compare(cfg, ref, s, got() if callable(got) else got)
        if why:
            wrong += 1
            worst = worst or why
    return {"checked": len(answers), "wrong_answers": wrong, "worst": worst}


def compare(cfg: dict, ref: Reference, s: int, got) -> str | None:
    """None where the served answer for ``s`` equals the reference's, or
    what differs."""
    have = np.asarray(answer_vertices(got))
    order = np.argsort(have, kind="stable")
    if cfg["arity"] == 2:
        want = ref.reach(s)
        if np.array_equal(have[order], want):
            return None
        return f"reach({s}): served {len(have)} vertices, reference {len(want)}"
    dst, dist = ref.dpath(s)
    vals = np.asarray(got[1])[order]
    if np.array_equal(have[order], dst) and np.array_equal(
            vals, dist.astype(np.int64)):
        return None
    return (f"dpath({s}): served {len(have)} destinations, reference "
            f"{len(dst)}")


def device_report(devices, trace) -> dict:
    d0 = devices[0]
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    rep = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    if trace is not None:
        busy = trace.busy_s
        rep["busy_s"] = sum(busy) / len(busy) if busy else 0.0
        rep["window_s"] = trace.window_s
    return rep


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, devices, cell=None) -> dict:
    """One run; returns the result object (the last line of stdout).

    ``cell`` overrides what ``BENCHMARK.json`` names (tests shrink it)."""
    import jax
    from repro.core.engine import fixpoint_trace_count

    cell = cell or spec.load(root, workload)
    cfg, mix = cell.config, cell.mix
    graph = graph500.generate(cfg, seed)
    log(f"graph: 2^{cfg['scale']} vertices, {len(graph.arcs)} arcs")
    tracer = profiled_tracer() if trace else None
    front = build(cfg, graph, tracer=tracer)
    svc = front.svc
    warm_up(svc, cfg, graph)
    flush_log = FlushLog(svc) if trace else None
    due, srcs = traffic.schedule(mix, seconds, graph, seed)
    compiles = CompileCounter()
    retraces0 = fixpoint_trace_count()
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s}; {len(due)} queries, "
        f"{int((due >= 0).sum())} due in the window")

    if trace:
        tracer.clear()  # the warm-up's spans
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # no event per Python call
        jax.profiler.start_trace(tdir, profiler_options=opts)
        window_ann = jax.profiler.TraceAnnotation(xtrace.WINDOW)
        window_ann.__enter__()
    sample = sample_sources(srcs[due >= 0], cfg["check_sample"], seed)
    pending = Outstanding()
    t0 = time.perf_counter() + mix["preroll_s"] + 0.05
    t1 = t0 + seconds
    recs = drive(front, cfg, due, srcs, t0, sample,
                 graph.n if trace else 0, pending)
    pending.wait(t1 + mix["drain_s"] - time.perf_counter())
    t_end = time.perf_counter()
    reduced = None
    if trace:
        window_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        reduced = xtrace.reduce_trace(xtrace.find_xplane(tdir), HOST_SPANS)
        shutil.rmtree(tdir, ignore_errors=True)
    n_compiles, compiled = compiles.count, compiles.names
    compiles.close()
    retraces = fixpoint_trace_count() - retraces0
    device = device_report(devices, reduced)
    front.close()
    counters = dict(vars(front.stats))

    def served(r: Rec) -> bool:  # answered by the end of the drain
        return r.ok and r.done <= t_end

    window = [r for r in recs if t0 <= r.due < t1]
    answered = [r for r in window if served(r)]
    lat = [(r.done - r.due) if served(r) else (t_end - r.due) for r in window]
    in_window = sum(1 for r in recs if served(r) and t0 <= r.done <= t1)
    shed = sum(r.shed for r in window)
    unanswered = len(window) - len(answered) - shed
    late = [r.submitted - r.due for r in recs]
    info = {"offered": len(recs), "due_in_window": len(window),
            "shed": shed, "unanswered": unanswered,
            "generator_late_p95_s": percentile(late, 95) if late else 0.0,
            "generator_late_max_s": max(late) if late else 0.0,
            "compiles_in_window": n_compiles, "fixpoint_retraces": retraces,
            "compiled_in_window": sorted(set(compiled))[:20],
            "flushes": counters["flushes"],
            "flushed_queries": counters["flushed_queries"],
            "short_circuits": counters["short_circuits"]}
    log(f"window: {info}")

    result = {"correct": False, "attempted": len(window),
              "failed": len(window) - len(answered)}
    if trace:
        record = layer_record(cell, graph, recs, counters, tracer, flush_log,
                              reduced, devices)
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(root, m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {"answers_per_s": in_window / seconds,
                  "latency_p50_s": percentile(lat, 50) if lat else None,
                  "latency_p95_s": percentile(lat, 95) if lat else None,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if values.get(m["name"]) is not None}
    result["metrics"] = metrics
    result["device"] = device
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced.device_ops,
                               "idle_gaps": reduced.idle_gaps}
    result["window"] = info
    del front, svc
    t_check = time.perf_counter()
    got = check(cfg, graph, {r.src: r.answer for r in answered if r.keep})
    log(f"check took {time.perf_counter() - t_check} s")
    checks = {"wrong_answers": {"value": got["wrong_answers"], "limit": 0},
              "unanswered": {"value": unanswered, "limit": 0}}
    result["correct"] = bool(window) and all(
        c["value"] <= c["limit"] for c in checks.values())
    if got["worst"]:
        log(f"first wrong answer: {got['worst']}")
    log(f"checked {got['checked']} answers against the reference")
    for k, c in checks.items():
        log(f"check: {k} {c['value']} (limit {c['limit']})")
    result["checks"] = checks
    return result


def layer_record(cell, graph, recs, counters, tracer, flush_log, reduced,
                 devices) -> dict:
    """What the per-layer metric readers read (``bench/metrics/*.py``)."""
    from .peaks import peaks_for

    cfg = cell.config
    reached = {r.src: r.reached for r in recs if r.reached is not None}
    deg = graph.out_degree
    weighted = graph.arcs.shape[1] == 3
    flushes = []
    for live, hits in flush_log.flushes:
        known = [(s, np.flatnonzero(np.unpackbits(reached[s],
                                                     count=graph.n)))
                 for s in live if s in reached]
        flushes.append({"live": len(live), "launch_hits": hits,
                        "useful_bytes": work.flush_bytes(deg, known, weighted)
                        if len(known) == len(live) else None})
    return {
        "max_batch": cfg["max_batch"],
        "queries": sum(1 for r in recs if not r.shed),
        "counters": counters,
        "flushes": flushes,
        "spans": tracer.spans() if tracer is not None else [],
        "busy_s": reduced.busy_s if reduced is not None else [],
        "window_s": reduced.window_s if reduced is not None else 0.0,
        "peaks": peaks_for(devices[0].device_kind)
        if devices[0].platform == "tpu" else None,
    }
