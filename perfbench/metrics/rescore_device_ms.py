"""Device time of the dispatch stage's whole-queue rescore (named scope
``stage/dispatch/rescore``) per chunk."""


def read(rec):
    t = rec.trace
    if t is None or not rec.traced_calls:
        return None
    s = t.scope_s("stage/dispatch/rescore/")
    return 1e3 * s / rec.traced_calls if s > 0 else None
