"""One less the device's busy share of the traced window (pre-roll, window
and drain), averaged over the chips."""


def read(rec: dict):
    busy, window = rec["busy_s"], rec["window_s"]
    if not busy or not window:
        return None
    return 1.0 - sum(busy) / len(busy) / window
