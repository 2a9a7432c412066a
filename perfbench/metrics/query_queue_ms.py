"""Mean over the traced query batches of the host time from the end of
the interval's ``ServeSession.take`` span (the interval's arrivals handed
to the session) to the start of the batch's ``ServeSession.query_batch``
span: how long a batch waits behind the earlier batches of its interval."""
import numpy as np

from perfbench.metrics.query_gap_ms import BATCH, host_spans


def read(rec):
    t = rec.trace
    if t is None:
        return None
    takes = host_spans(t, "ServeSession.take")
    batches = host_spans(t, BATCH)
    if not len(takes) or not len(batches):
        return None
    waits = []
    for start, _ in batches:
        ends = takes[takes[:, 1] <= start, 1]
        if len(ends):
            waits.append(start - ends.max())
    return float(np.mean(waits)) / 1e6 if waits else None
