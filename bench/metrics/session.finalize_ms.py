"""Mean host time of a flush's finalize, in milliseconds: the
``finalize_batch`` span less the ``device_sync`` spans nested in it (the
wait for the device table), so what is left is the transfer's end,
answer formatting and the cache fill."""


def read(rec: dict):
    spans = rec["spans"]
    syncs = [s for s in spans if s["name"] == "device_sync"]
    own = []
    for f in spans:
        if f["name"] != "finalize_batch":
            continue
        end = f["ts"] + f["dur"]
        wait = sum(s["dur"] for s in syncs if s["tid"] == f["tid"]
                   and f["ts"] <= s["ts"] and s["ts"] + s["dur"] <= end)
        own.append(f["dur"] - wait)
    if not own:
        return None
    return sum(own) / len(own) / 1e3  # span times are microseconds
