"""Device idle time outside every ``ServeSession.query_batch`` host span,
per traced ``ServeSession.run`` call: the chip waiting on the rest of the
serving loop (the chunk's launch, the take, the fold, the harvest of the
fetched URLs, the end-of-call report) and on the benchmark between calls.
With ``query_gap_ms`` it splits the idle time of ``device_idle_pct.serve``:
query_gap_ms x batches + interval_gap_ms x calls."""
from perfbench.metrics.query_gap_ms import BATCH, host_spans, idle_ns


def read(rec):
    t = rec.trace
    if t is None or not rec.traced_calls:
        return None
    if not len(host_spans(t, "ServeSession.run")):
        return None
    outside = idle_ns(t) - idle_ns(t, host_spans(t, BATCH))
    return outside / rec.traced_calls / 1e6
