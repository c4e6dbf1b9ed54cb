"""Median over the run's queries of the time their flush spent launched
and not yet synced, in milliseconds: per flush number, from the start of
its ``launch_batch`` span to the end of its last ``device_sync`` span,
each flush counted once per query it carried (``launch_batch``'s
``batch``).  This is the wait behind the flushes already on the device
and the flush's own fixpoint."""
import statistics


def read(rec: dict):
    launches, ends = {}, {}
    for s in rec["spans"]:
        flush = (s.get("args") or {}).get("flush")
        if flush is None:
            continue
        if s["name"] == "launch_batch":
            launches[flush] = s
        elif s["name"] == "device_sync":
            ends[flush] = max(ends.get(flush, 0.0), s["ts"] + s["dur"])
    per_query = []
    for flush, s in launches.items():
        if flush in ends:
            ms = (ends[flush] - s["ts"]) / 1e3  # span times are microseconds
            per_query += [ms] * s["args"]["batch"]
    if not per_query:
        return None
    return statistics.median(per_query)
