"""Spill tier: share of the window's volume restores served by a
decode-ahead prefetch (``kv_prefetch_hits / kv_restores``), in %."""


def read(run):
    c = run.counters
    if not c.get("kv_restores"):
        return None
    return 100.0 * c["kv_prefetch_hits"] / c["kv_restores"]
