"""Queries answered during the window over its wall time."""


def read(rec):
    if not rec.due_in_window:
        return None
    return rec.queries_answered / rec.window_s
