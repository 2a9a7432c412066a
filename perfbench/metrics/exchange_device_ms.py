"""Device time of the URL exchange (named scope
``stage/dispatch/exchange``: the bucket packing, the all_to_all and the
unpacking) per chunk, as a mean over the devices. On several chips the
all_to_all holds each chip until the slowest has sent, so the wait is in
it too. None where the program has no such scope."""


def read(rec):
    t = rec.trace
    if t is None or not rec.traced_calls:
        return None
    s = t.scope_s("stage/dispatch/exchange/")
    return 1e3 * s / rec.traced_calls if s > 0 else None
