"""The program's own spans and stage scopes on the profiler's clock
(repro/obs/trace.py, core/crawler.py, DESIGN.md §17).

The contracts pinned here:
  * the compiled fused chunk names each pipeline stage
    (``stage/allocate`` ... ``stage/dispatch/rescore``), with the kernel
    scopes nested beneath the stage that launches them;
  * a tracer that does not record still opens a profiler annotation for
    every span, and appends no event;
  * a ``ServeSession.run`` under ``jax.profiler.trace`` leaves its spans on
    the host plane, nested in ``ServeSession.run``, one
    ``ServeSession.query_batch`` per batch;
  * with telemetry off the sessions add no device read and no host wait.
"""
import glob
import math
import re

import jax
import pytest

from repro.api import CrawlSession
from repro.api.session import chunk_program
from repro.configs import get_reduced
from repro.configs.base import scaled
from repro.kernels import registry
from repro.obs import trace as OT
from repro.serve import QueryLoad, ServeSession

CFG = scaled(get_reduced("webparf"), ordering="opic_url", link_pop_bias=1.0,
             telemetry=False)
IV = CFG.dispatch_interval
BATCH = 4


@pytest.fixture(autouse=True)
def _own_telemetry_knob(monkeypatch):
    monkeypatch.delenv("REPRO_TELEMETRY", raising=False)


def _serve_session(cfg=CFG):
    return ServeSession(cfg, load=QueryLoad(cfg, qps=6.0, seed=0),
                        index_capacity=256, doc_len=16, vocab=512, top_k=4,
                        query_batch=BATCH)


@pytest.mark.parametrize("fused,kernel", [(True, "dedup_deposit"),
                                          (False, "bloom")])
def test_chunk_names_stage_scopes(monkeypatch, fused, kernel):
    monkeypatch.setattr(registry, "_ANNOTATE", True)
    jax.clear_caches()          # kernels traced earlier lack their scopes
    cfg = scaled(CFG, fused_dispatch=fused)
    sess = CrawlSession(cfg)
    hlo = chunk_program(cfg, sess.mesh, axes=sess.axes).lower(
        sess.state).compile().as_text()
    ops = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in ("allocate", "fetch_analyze", "extract", "dispatch",
                  "dispatch/rescore", "opic_url_update"):
        assert any(f"stage/{scope}/" in o for o in ops), scope
    # the fetch steps run in the scan's body, the dispatch step after it
    assert any("while/body" in o and "stage/allocate/" in o for o in ops)
    assert any(re.search(rf"stage/dispatch/(.*/)?kernel/{kernel}\.", o)
               for o in ops), kernel


class _Annotation:
    """Stands in for jax.profiler.TraceAnnotation and logs what it saw."""
    log = []

    def __init__(self, name, **args):
        self.name, self.args = name, dict(args)

    def __enter__(self):
        self.log.append(("enter", self.name, dict(self.args)))
        return self

    def set_metadata(self, **args):
        self.args.update(args)

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, dict(self.args)))


@pytest.mark.parametrize("record", [False, True])
def test_span_always_annotates(monkeypatch, record):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    _Annotation.log = []
    tracer = OT.Tracer(record=record)
    with tracer.span("ServeSession.take", "serve", interval=3) as args:
        args["queries"] = 5
    tracer.instant("heal", "fault")
    tracer.counter("frontier_depth", {"shard0": 1.0})
    assert _Annotation.log == [
        ("enter", "ServeSession.take", {"interval": 3}),
        ("exit", "ServeSession.take", {"interval": 3, "queries": 5})]
    if record:
        assert [(e.name, e.ph, e.args) for e in tracer.events] == [
            ("ServeSession.take", "X", {"interval": 3, "queries": 5}),
            ("heal", "i", {}), ("frontier_depth", "C", {"shard0": 1.0})]
    else:
        assert tracer.events == []


def _host_spans(logdir):
    from jax.profiler import ProfileData
    path = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)[0]
    with open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name.split("#")[0],
             dict(e.stats))
            for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events
            if "Session." in e.name]


def test_serve_spans_reach_the_profiler(tmp_path):
    sess = _serve_session()
    sess.run(IV, recall=False)              # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        rep = sess.run(2 * IV, recall=False)
    assert sess.tracer.events == []         # telemetry off: nothing kept
    spans = _host_spans(tmp_path)
    by = {}
    for s, e, name, args in spans:
        by.setdefault(name, []).append((s, e, args))
    assert set(by) == {
        "ServeSession.run", "ServeSession.chunk", "ServeSession.take",
        "ServeSession.query_batch", "ServeSession.fold",
        "ServeSession.harvest", "ServeSession.report",
        "CrawlSession.run_chunk"}
    (r0, r1, _), = by["ServeSession.run"]
    assert all(r0 <= s and e <= r1 for s, e, _, _ in spans)
    assert len(by["ServeSession.chunk"]) == 2
    for s, e, _ in by["CrawlSession.run_chunk"]:
        assert any(c0 <= s and e <= c1
                   for c0, c1, _ in by["ServeSession.chunk"])
    taken = [a["queries"] for _, _, a in by["ServeSession.take"]]
    assert sum(taken) == rep.n_queries > BATCH
    batches = by["ServeSession.query_batch"]
    assert len(batches) == sum(math.ceil(n / BATCH) for n in taken)
    assert sum(a["n"] for _, _, a in batches) == rep.n_queries


class _Counter:
    """Counts host waits (jax.block_until_ready) and device reads (an
    array's host value: np.asarray, int(), tolist())."""

    def __init__(self, monkeypatch):
        from jax._src.array import ArrayImpl
        self.waits = self.reads = 0
        wait, value = jax.block_until_ready, ArrayImpl._value

        def counted_wait(x):
            self.waits += 1
            return wait(x)

        def counted_read(arr):
            self.reads += 1
            return value.fget(arr)
        monkeypatch.setattr(jax, "block_until_ready", counted_wait)
        monkeypatch.setattr(ArrayImpl, "_value", property(counted_read))


def test_telemetry_off_adds_no_read_or_wait(monkeypatch):
    crawl = CrawlSession(CFG)
    crawl.run(IV)                            # compile both paths first
    crawl.run(IV, mode="eager")
    serve = _serve_session()
    serve.run(IV, recall=False)
    count = _Counter(monkeypatch)
    crawl.run_chunk()
    for _ in range(IV):
        crawl.step()
    assert (count.waits, count.reads) == (0, 0)
    assert crawl.tracer.events == []
    launches = []
    query_fn = serve._query_fn
    serve._query_fn = lambda *a: (launches.append(1), query_fn(*a))[1]
    rep = serve.run(2 * IV, recall=False)
    # the chunk's wait and each batch's: the loop's own, none added
    assert len(launches) >= math.ceil(rep.n_queries / BATCH) > 0
    assert count.waits == 2 + len(launches)
    assert serve.tracer.events == []
