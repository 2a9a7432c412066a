"""95th percentile over every query due in the window, each timed from its
wall due time until its answer is on the host (late answers count their
wait)."""
import numpy as np


def read(rec):
    if not len(rec.latency_ms):
        return None
    return float(np.percentile(rec.latency_ms, 95))
