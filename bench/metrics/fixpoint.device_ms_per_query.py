"""Device busy time of the traced run, summed over the chips, per live
query launched into the fixpoint, in milliseconds.  Busy time is the union
of the device's operations, so no program name is needed to find it."""


def read(rec: dict):
    live = sum(f["live"] for f in rec["flushes"])
    busy = sum(rec["busy_s"])
    if not live or not busy:
        return None
    return busy / live * 1e3
