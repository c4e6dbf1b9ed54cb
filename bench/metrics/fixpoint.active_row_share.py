"""Share of the fixpoint's row steps that still had work: over the run's
``device_sync`` spans, the rows unconverged entering each step, summed
over the loop (``active_rows``), over the rows launched times the
iterations (``pad`` × ``iterations``).  A flush whose loop did not count
rows (``active_rows`` -1) is left out."""


def read(rec: dict):
    active = steps = 0
    for s in rec["spans"]:
        args = s.get("args") or {}
        if s["name"] == "device_sync" and args.get("active_rows", -1) >= 0:
            active += args["active_rows"]
            steps += args["pad"] * args["iterations"]
    if not steps:
        return None
    return active / steps
