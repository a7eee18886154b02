"""Mean plan-cache lookup time per task (``RunRecord.cache_lookup_s``, host
clock around ``cache.lookup``, which returns host values, so the device
top-k is inside it)."""


def read(run):
    if not run.records:
        return None
    return 1000.0 * sum(r.cache_lookup_s for r in run.records) / len(run.records)
