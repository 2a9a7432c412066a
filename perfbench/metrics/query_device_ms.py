"""Device time of the query program per query batch."""


def read(rec):
    t = rec.trace
    if t is None or not rec.traced_query_batches:
        return None
    s = t.module_s("query_local")
    return 1e3 * s / rec.traced_query_batches if s > 0 else None
