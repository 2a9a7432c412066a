"""Device time of the fused chunk's extract stage (named scope
``stage/extract``: parse outlinks, canonicalise, stage them for the
dispatch) per chunk."""


def read(rec):
    t = rec.trace
    if t is None or not rec.traced_calls:
        return None
    s = t.scope_s("stage/extract/")
    return 1e3 * s / rec.traced_calls if s > 0 else None
