"""Useful bytes of the traced run's flushes (``bench/lib/work.py``) at the
chip's HBM peak, as a share in percent of the device busy time summed over
the chips.  Memory bounds this work: it does no arithmetic to speak of."""


def read(rec: dict):
    busy = sum(rec["busy_s"])
    useful = [f["useful_bytes"] for f in rec["flushes"]]
    if not busy or not useful or None in useful or rec["peaks"] is None:
        return None
    return 100.0 * sum(useful) / rec["peaks"]["hbm_bytes_per_s"] / busy
