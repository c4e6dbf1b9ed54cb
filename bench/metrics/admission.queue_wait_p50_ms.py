"""Median over the run's queries of the wait from submit to leaving the
admission queue, in milliseconds: the ``queue_wait_ms`` lists of the
``flush`` spans, one value per query."""
import statistics


def read(rec: dict):
    waits = [w for s in rec["spans"] if s["name"] == "flush"
             for w in (s.get("args") or {}).get("queue_wait_ms", ())]
    if not waits:
        return None
    return statistics.median(waits)
