"""Share of the fixpoint's rows that were padding: over the run's
``device_sync`` spans, the pad rows (``pad`` less the live ``rows``) over
all rows launched (``pad``)."""


def read(rec: dict):
    pad = live = 0
    for s in rec["spans"]:
        args = s.get("args") or {}
        if s["name"] == "device_sync" and "pad" in args:
            pad += args["pad"]
            live += args["rows"]
    if not pad:
        return None
    return (pad - live) / pad
