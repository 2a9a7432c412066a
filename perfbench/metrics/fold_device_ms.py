"""Device time of the index fold (the program that adds one interval's
pages to the index) per fold."""


def read(rec):
    t = rec.trace
    if t is None or not rec.traced_folds:
        return None
    s = t.module_s("add_local")
    return 1e3 * s / rec.traced_folds if s > 0 else None
