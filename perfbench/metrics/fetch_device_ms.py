"""Device time of the fused chunk's fetch and analyze stage (named scope
``stage/fetch_analyze``) per chunk."""


def read(rec):
    t = rec.trace
    if t is None or not rec.traced_calls:
        return None
    s = t.scope_s("stage/fetch_analyze/")
    return 1e3 * s / rec.traced_calls if s > 0 else None
