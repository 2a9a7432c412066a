"""Device time of the fused crawl chunk's executions per chunk."""


def read(rec):
    t = rec.trace
    if t is None or not rec.traced_calls:
        return None
    s = t.module_s("chunk_local")
    return 1e3 * s / rec.traced_calls if s > 0 else None
