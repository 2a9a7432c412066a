"""Device time of the fused chunk's dispatch stage (named scope
``stage/dispatch``: exchange, Bloom dedup, deposit, rescore) per chunk."""


def read(rec):
    t = rec.trace
    if t is None or not rec.traced_calls:
        return None
    s = t.scope_s("stage/dispatch/")
    return 1e3 * s / rec.traced_calls if s > 0 else None
