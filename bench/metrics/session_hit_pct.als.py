"""Session hits over hits plus misses across the ALS window."""


def read(run):
    hits = run.counters.get("session_hits", 0)
    total = hits + run.counters.get("session_misses", 0)
    return 100 * hits / total if total else None
