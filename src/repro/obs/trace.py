"""Span tracing on the profiler's clock + Chrome ``trace_event`` export
(DESIGN.md §17).

Every span enters a ``jax.profiler.TraceAnnotation`` — always, whatever
the session's telemetry says — so a ``jax.profiler`` capture shows the
host phases on the same clock as the device's operations. Outside a
capture a span costs a few microseconds and reads nothing from
the device. A span's arguments are host-known integers (queries in a
batch, batch index, interval number); no span ever waits for the device.
When the tracer *records* (the session's telemetry is on) each span is
also appended as an :class:`Event` for the Chrome/JSONL export, together
with instant markers for C4 fail/heal events and counter series sampled
from the load ledger at interval boundaries; the telemetry path then
blocks on the device result inside its spans, so recorded durations are
compute, not async-dispatch returns.

Span names carry the session that opens them, and they nest:

  * ``CrawlSession.run_chunk`` — one fused interval's launch;
    ``CrawlSession.step`` — one eager step; ``CrawlSession.checkpoint`` /
    ``CrawlSession.restore`` / ``CrawlSession.rebalance``;
  * ``ServeSession.run`` — one call, the parent of ``ServeSession.chunk``
    (the crawl chunk and its wait), ``ServeSession.take`` (the interval's
    arrivals), ``ServeSession.query_batch`` (one batch: launch, wait,
    top-k copy), ``ServeSession.fold`` (the index add),
    ``ServeSession.harvest`` (fetched URLs to the host) and
    ``ServeSession.report`` (the end-of-call counters).

Inside-jit structure is not faked with host clocks: the fused chunk names
its stages with ``jax.named_scope`` (``stage/allocate``,
``stage/fetch_analyze``, ``stage/extract``, ``stage/dispatch`` with
``stage/dispatch/rescore``, scenario stages by function name;
core/crawler.py), and ``kernels/registry.py`` wraps every resolved kernel
launch in ``kernel/<family>.<impl>`` when annotation is enabled, so device
profiles label each stage and kernel-family region.

Export formats:
  * ``.json``  — a Chrome ``trace_event`` document (``chrome://tracing`` /
    Perfetto loadable): ``X`` complete events for spans, ``i`` instants,
    ``C`` counters (one per-shard series per load metric). The load ledger
    itself is embedded under ``otherData.ledger`` so
    ``launch/trace_report.py`` can rebuild the shard-load timeline table
    from the file alone.
  * ``.jsonl`` — the same events one JSON object per line (stream-friendly).

``validate_chrome_trace`` is the structural schema check the tests and the
timeline reporter share.
"""
from __future__ import annotations

import dataclasses
import json
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Tuple

import jax


@dataclasses.dataclass
class Event:
    """One trace event, in (a host-side mirror of) trace_event terms."""
    name: str
    cat: str
    ph: str                      # "X" complete | "i" instant | "C" counter
    ts: float                    # seconds since the tracer's origin
    dur: float = 0.0             # seconds ("X" only)
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)
    tid: int = 0


class Tracer:
    """Opens profiler annotations around host phases and, when ``record``
    is on, accumulates :class:`Event` records (one list append per
    host-visible boundary — never inside jitted code)."""

    def __init__(self, *, record: bool = True):
        self.events: List[Event] = []
        self.record = bool(record)
        self._origin = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self._origin

    @contextmanager
    def span(self, name: str, cat: str = "stage", **args):
        """A profiler annotation around the body, and with ``record`` on a
        complete ("X") event. Yields the span's argument dict: keys the
        body adds (e.g. a count known only once the body ran) reach both
        the annotation and the event."""
        given = dict(args)
        t0 = self.now() if self.record else 0.0
        with jax.profiler.TraceAnnotation(name, **given) as ann:
            yield args
            extra = {k: v for k, v in args.items() if k not in given}
            if extra:
                ann.set_metadata(**extra)
        if self.record:
            self.events.append(Event(name=name, cat=cat, ph="X", ts=t0,
                                     dur=self.now() - t0, args=dict(args)))

    def instant(self, name: str, cat: str = "event", **args) -> None:
        if self.record:
            self.events.append(Event(name=name, cat=cat, ph="i",
                                     ts=self.now(), args=dict(args)))

    def counter(self, name: str, values: Dict[str, float],
                cat: str = "ledger") -> None:
        """One counter sample: ``values`` maps series name (e.g. ``shard0``)
        to the sampled value — Chrome renders them as stacked area rows."""
        if self.record:
            self.events.append(Event(name=name, cat=cat, ph="C",
                                     ts=self.now(),
                                     args={k: float(v) for k, v in
                                           values.items()}))

    # -- export -------------------------------------------------------------

    def chrome_events(self) -> List[Dict[str, Any]]:
        out = []
        for e in self.events:
            ev = {"name": e.name, "cat": e.cat, "ph": e.ph, "pid": 0,
                  "tid": e.tid, "ts": round(e.ts * 1e6, 3)}
            if e.ph == "X":
                ev["dur"] = round(e.dur * 1e6, 3)
            if e.ph == "i":
                ev["s"] = "g"                    # global-scope instant
            if e.args:
                ev["args"] = e.args
            out.append(ev)
        return out

    def to_chrome(self, telemetry=None) -> Dict[str, Any]:
        """The full trace document; ``telemetry`` (a CrawlTelemetry or
        anything with steps/rows/names/interval) embeds the load ledger
        under ``otherData.ledger`` for the timeline reporter."""
        doc: Dict[str, Any] = {"traceEvents": self.chrome_events(),
                               "displayTimeUnit": "ms"}
        if telemetry is not None:
            doc["otherData"] = {"ledger": ledger_payload(telemetry)}
        return doc

    def write(self, path: str, telemetry=None) -> str:
        """Write ``.jsonl`` (one event per line, ledger as a trailing
        ``otherData`` line) or Chrome-trace ``.json`` (anything else)."""
        doc = self.to_chrome(telemetry)
        with open(path, "w") as f:
            if path.endswith(".jsonl"):
                for ev in doc["traceEvents"]:
                    f.write(json.dumps(ev) + "\n")
                if "otherData" in doc:
                    f.write(json.dumps({"otherData": doc["otherData"]}) + "\n")
            else:
                json.dump(doc, f, indent=1)
                f.write("\n")
        return path


def ledger_payload(telemetry) -> Dict[str, Any]:
    """JSON-serializable ledger block (the reporter's table source)."""
    import numpy as np
    return {
        "names": list(telemetry.names),
        "interval": int(telemetry.interval),
        "steps": np.asarray(telemetry.steps).astype(int).tolist(),
        "rows": np.asarray(telemetry.rows, float).round(4).tolist(),
    }


_REQUIRED = ("name", "ph", "ts", "pid", "tid")


def validate_chrome_trace(doc: Any) -> List[str]:
    """Structural trace_event schema check; returns a list of violations
    (empty = valid). Shared by tests/test_obs.py and the timeline CLI."""
    errs = []
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["document must be an object with a traceEvents array"]
    events = doc["traceEvents"]
    if not isinstance(events, list):
        return ["traceEvents must be an array"]
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            errs.append(f"event {i}: not an object")
            continue
        for k in _REQUIRED:
            if k not in ev:
                errs.append(f"event {i} ({ev.get('name')}): missing {k!r}")
        ph = ev.get("ph")
        if ph not in ("X", "B", "E", "i", "I", "C", "M"):
            errs.append(f"event {i}: unknown phase {ph!r}")
        if ph == "X" and not isinstance(ev.get("dur"), (int, float)):
            errs.append(f"event {i} ({ev.get('name')}): X event needs "
                        f"numeric dur")
        if ph == "C" and not isinstance(ev.get("args"), dict):
            errs.append(f"event {i} ({ev.get('name')}): C event needs args")
        if not isinstance(ev.get("ts"), (int, float)):
            errs.append(f"event {i} ({ev.get('name')}): ts must be numeric")
    return errs


def span_totals(events) -> Dict[Tuple[str, str], Tuple[int, float]]:
    """Aggregate spans -> {(cat, name): (count, total seconds)}. Accepts
    :class:`Event` objects or chrome-format dicts."""
    out: Dict[Tuple[str, str], Tuple[int, float]] = {}
    for e in events:
        if isinstance(e, Event):
            ph, key, dur = e.ph, (e.cat, e.name), e.dur
        else:
            ph = e.get("ph")
            key = (e.get("cat", ""), e.get("name", ""))
            dur = float(e.get("dur", 0.0)) * 1e-6
        if ph != "X":
            continue
        n, tot = out.get(key, (0, 0.0))
        out[key] = (n + 1, tot + dur)
    return out
