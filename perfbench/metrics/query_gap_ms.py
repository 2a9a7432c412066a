"""Device idle time inside the ``ServeSession.query_batch`` host spans
(padding, launch, the wait for the answer, the copy of the top-k to the
host) per traced query batch, the batches counted from the spans.

The helpers here read the program's host spans from the trace; the other
serving-loop readers (``interval_gap_ms``, ``query_queue_ms``) use them."""
import numpy as np

from perfbench.trace import gaps_ns

BATCH = "ServeSession.query_batch"


def host_spans(t, name: str) -> np.ndarray:
    """(start, end) ns of the host spans called ``name`` (annotation
    arguments after a ``#`` ignored), clipped to the traced window and
    sorted by start."""
    out = [(max(s, t.t0), min(e, t.t1)) for s, e, n in t.host
           if n.split("#", 1)[0] == name]
    out = [(s, e) for s, e in out if e > s]
    return np.array(sorted(out), float).reshape(-1, 2)


def idle_ns(t, spans=None) -> float:
    """Device idle ns in the window (mean over the cell's devices); with
    ``spans``, only the idle time inside their union."""
    per = []
    for d in t.devices:
        gaps = np.array(gaps_ns(d.ops, t.t0, t.t1), float).reshape(-1, 2)
        if spans is None:
            per.append(float((gaps[:, 1] - gaps[:, 0]).sum()))
            continue
        total, cur = 0.0, -np.inf
        for s, e in spans:            # sorted by start; count overlaps once
            s = max(s, cur)
            if e <= s:
                continue
            total += float(np.clip(np.minimum(gaps[:, 1], e)
                                   - np.maximum(gaps[:, 0], s), 0, None).sum())
            cur = e
        per.append(total)
    return float(np.mean(per)) if per else 0.0


def read(rec):
    t = rec.trace
    if t is None:
        return None
    batches = host_spans(t, BATCH)
    if not len(batches):
        return None
    return idle_ns(t, batches) / len(batches) / 1e6
