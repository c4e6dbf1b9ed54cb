"""Query-serving subsystem: resident Datalog sessions over the core engines.

``DatalogService`` (``session.py``) loads a program + EDB once and answers
query streams with memoized plans, micro-batched dense fixpoints
(``batch.py``), an LRU result cache (``cache.py``), and incremental monotone
EDB appends that resume — not recompute — cached fixpoints
(``incremental.py``).  ``AsyncDatalogService`` (``admission.py``) puts a
continuous-batching admission front-end over it: callers submit single
queries and get futures while a dispatcher coalesces arrivals into batched
fixpoints with device/host overlap.  ``python -m repro.service.serve`` is
the CLI front-end; ``benchmarks/bench_serve.py`` measures queries/sec.

Observability (``repro.obs``) threads through the whole stack:
``DatalogService(tracer=True)`` records Chrome-exportable spans,
``metrics``/``svc.metrics`` is the unified counter/histogram registry
(Prometheus + JSON exporters), and ``explain()["relations"][pred]
["fixpoint"]`` totals the fixpoint loops' own row and Δ counters.
``MetricsRegistry`` and ``Tracer`` are re-exported here for convenience.

Durability (``durable.py``): ``DatalogService(durable_dir=...)`` write-ahead
logs every append, snapshots the hot serving state through the background
checkpoint writer, and recovers warm (newest complete snapshot + WAL replay
through the append-resume path) with graceful degradation on corruption.
"""
from ..obs.metrics import MetricsRegistry
from ..obs.trace import Tracer
from .admission import AdmissionStats, AsyncDatalogService, QueueFullError
from .cache import CacheEntry, LRUCache
from .durable import DurabilityManager, WriteAheadLog
from .session import DatalogService, ServiceStats

__all__ = ["AdmissionStats", "AsyncDatalogService", "CacheEntry",
           "DatalogService", "DurabilityManager", "LRUCache",
           "MetricsRegistry", "QueueFullError", "ServiceStats", "Tracer",
           "WriteAheadLog"]
