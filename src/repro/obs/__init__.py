"""repro.obs — the observability layer (DESIGN.md §17).

Three pieces, threaded through ``CrawlSession``/``ServeSession``:

  * ``ledger``  — the per-shard, per-step load ledger: device-resident
    metric rows snapshotted INSIDE the fused ``run_chunk`` scan (an extra
    stacked output — the hot path traces no host callbacks), accumulated
    host-side as a ``(n_records, n_shards, n_metrics)`` time-series;
  * ``trace``   — span tracing around every boundary the host can see
    (chunk launches, eager steps, checkpoint/restore, the serve loop's
    phases), always on the ``jax.profiler`` clock as annotations, and
    with telemetry on recorded for export as Chrome ``trace_event`` JSON
    and JSONL;
  * ``health``  — derived skew/health metrics over the ledger (load
    imbalance factor, comm-per-page trend, frontier growth, freshness
    lag), surfaced as ``CrawlReport.telemetry`` / ``ServeReport.telemetry``.

Telemetry is OFF by default (``CrawlConfig.telemetry``); off means the
compiled programs and the crawl trajectory are bit-for-bit the untraced
ones (tests/test_obs.py pins both directions). ``REPRO_TELEMETRY=1`` flips
it on globally — the CI invariants matrix replays the whole suite that way.
"""
from __future__ import annotations

import os

from repro.obs.health import CrawlTelemetry, ServeTelemetry
from repro.obs.ledger import (LEDGER_BASE, LedgerBuffer, ledger_metrics,
                              snapshot_local)
from repro.obs.trace import Event, Tracer, validate_chrome_trace

__all__ = [
    "CrawlTelemetry", "ServeTelemetry", "Event", "Tracer",
    "LEDGER_BASE", "LedgerBuffer", "ledger_metrics", "snapshot_local",
    "telemetry_enabled", "validate_chrome_trace",
]


def telemetry_enabled(cfg) -> bool:
    """The one place the config flag and the env knob are combined: sessions
    call this at build time. ``REPRO_TELEMETRY=1`` (the CI matrix cell)
    turns telemetry on for every session regardless of config."""
    if bool(getattr(cfg, "telemetry", False)):
        return True
    return os.environ.get("REPRO_TELEMETRY", "0") not in ("", "0")
