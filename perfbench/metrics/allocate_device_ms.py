"""Device time of the fused chunk's allocate stage (named scope
``stage/allocate``: pop each row's best URLs, give back the rest) per
chunk."""


def read(rec):
    t = rec.trace
    if t is None or not rec.traced_calls:
        return None
    s = t.scope_s("stage/allocate/")
    return 1e3 * s / rec.traced_calls if s > 0 else None
