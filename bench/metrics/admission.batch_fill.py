"""Mean share of ``max_batch`` that a flush fills: flushed queries over
flushes over ``max_batch``, from the admission counters of the run."""


def read(rec: dict):
    c = rec["counters"]
    if not c["flushes"]:
        return None
    return c["flushed_queries"] / c["flushes"] / rec["max_batch"]
