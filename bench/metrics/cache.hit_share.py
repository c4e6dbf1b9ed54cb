"""Share of the run's queries the result cache answered: at submit
(admission short-circuits) or at launch, over the queries admitted."""


def read(rec: dict):
    if not rec["queries"]:
        return None
    hits = rec["counters"]["short_circuits"] + sum(
        f["launch_hits"] for f in rec["flushes"])
    return hits / rec["queries"]
