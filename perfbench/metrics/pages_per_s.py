"""Pages fetched in the window over its wall time, all chips together."""


def read(rec):
    return rec.pages / rec.window_s
