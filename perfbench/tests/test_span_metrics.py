"""The stage and serving-loop readers on a small hand-written profile whose
answers are worked out by hand.

Window [1, 21] us. Device ops (us): allocate [0, 3] (clipped to [1, 3]),
fetch [5, 6], extract [6.5, 7], a dedup kernel inside the dispatch stage
[10, 13], the rescore [13, 14], a query op [19, 20]: busy 8.5, idle 11.5,
in gaps [3, 5], [6, 6.5], [7, 10], [14, 19], [20, 21].

Host spans (us): ``ServeSession.run`` [0.5, 25]; takes [2, 3] and
[14, 15]; query batches [4, 8], [9, 12], [18, 24] (past the window's end,
so [18, 21]) and [22, 23] (outside it), two of them with annotation
arguments in their names.
"""
from types import SimpleNamespace

import pytest

from perfbench import spec as SP
from perfbench import trace as TR

SCOPE = "jit(chunk_local)/while/body/closed_call/stage/"

PROFILE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 24500000 }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 4000000 duration_ps: 4000000 }
    events { metadata_id: 5 offset_ps: 9000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 14000000 duration_ps: 1000000 }
    events { metadata_id: 6 offset_ps: 18000000 duration_ps: 6000000 }
    events { metadata_id: 5 offset_ps: 22000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "ServeSession.run" } }
  event_metadata { key: 3 value { id: 3 name: "ServeSession.take" } }
  event_metadata { key: 4 value { id: 4
                   name: "ServeSession.query_batch#n=16,batch=0#" } }
  event_metadata { key: 5 value { id: 5 name: "ServeSession.query_batch" } }
  event_metadata { key: 6 value { id: 6
                   name: "ServeSession.query_batch#n=16#" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000
             stats { metadata_id: 1 str_value: "%(s)sallocate/select_n" } }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000
             stats { metadata_id: 1 str_value: "%(s)sfetch_analyze/add" } }
    events { metadata_id: 3 offset_ps: 6500000 duration_ps: 500000
             stats { metadata_id: 1 str_value: "%(s)sextract/xor" } }
    events { metadata_id: 4 offset_ps: 10000000 duration_ps: 3000000
             stats { metadata_id: 1 str_value:
             "%(s)sdispatch/jit(dedup_deposit)/kernel/dedup_deposit.ref/x" } }
    events { metadata_id: 5 offset_ps: 13000000 duration_ps: 1000000
             stats { metadata_id: 1 str_value: "%(s)sdispatch/rescore/clip" } }
    events { metadata_id: 6 offset_ps: 19000000 duration_ps: 1000000
             stats { metadata_id: 1 str_value: "jit(query_local)/dot" } } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 7 offset_ps: 1000000 duration_ps: 14000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.3" } }
  event_metadata { key: 4 value { id: 4 name: "fusion.4" } }
  event_metadata { key: 5 value { id: 5 name: "fusion.5" } }
  event_metadata { key: 6 value { id: 6 name: "convert_reduce_fusion" } }
  event_metadata { key: 7 value { id: 7 name: "jit_chunk_local(7)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
""" % {"s": SCOPE}


def _trace(text=PROFILE):
    from jax.profiler import ProfileData
    return TR.from_profile(ProfileData.from_text_proto(text), n_devices=1)


@pytest.fixture(scope="module")
def rec():
    return SimpleNamespace(trace=_trace(), traced_calls=2)


def test_trace_idle_as_worked_out(rec):
    t = rec.trace
    assert t.window_s == pytest.approx(20e-6)
    assert t.busy_s == pytest.approx(8.5e-6)
    # the kernel scope nested under the stage scope is still found
    assert t.scope_s("kernel/dedup_deposit.") == pytest.approx(3e-6)


@pytest.mark.parametrize("metric,want_us", [
    ("allocate_device_ms", 2 / 2),       # [1, 3] over two chunks
    ("fetch_device_ms", 1 / 2),
    ("extract_device_ms", 0.5 / 2),
    ("dispatch_device_ms", 4 / 2),       # the kernel and the rescore
    ("rescore_device_ms", 1 / 2),
    # idle in the batches: [4, 5] + [6, 6.5] + [7, 8], [9, 10], [18, 19] +
    # [20, 21] = 5.5 over 3 batches (the one past the window not counted)
    ("query_gap_ms", 5.5 / 3),
    # the rest of the 11.5 idle over two calls
    ("interval_gap_ms", (11.5 - 5.5) / 2),
    # batch starts 4, 9, 18 less the last take's end before each: 3, 3, 15
    ("query_queue_ms", (1 + 6 + 3) / 3),
])
def test_reader_worked_out(rec, metric, want_us):
    assert SP.reader(metric)(rec) == pytest.approx(want_us * 1e-3)


def test_serving_gaps_split_the_idle_time(rec):
    """query_gap_ms x batches + interval_gap_ms x calls is the idle time
    that device_idle_pct.serve reads."""
    batches = 3
    idle_ms = (SP.reader("query_gap_ms")(rec) * batches
               + SP.reader("interval_gap_ms")(rec) * rec.traced_calls)
    pct = SP.reader("device_idle_pct.serve")(rec)
    assert idle_ms == pytest.approx(pct / 100 * rec.trace.window_s * 1e3)


def test_host_spans_match_the_name_before_the_args(rec):
    from perfbench.metrics.query_gap_ms import host_spans
    got = host_spans(rec.trace, "ServeSession.query_batch")
    assert got.tolist() == [[4000, 8000], [9000, 12000], [18000, 21000]]
    assert host_spans(rec.trace, "ServeSession.run").tolist() == \
        [[1000, 21000]]
    assert not len(host_spans(rec.trace, "ServeSession"))


@pytest.mark.parametrize("metric", [
    "allocate_device_ms", "fetch_device_ms", "extract_device_ms",
    "dispatch_device_ms", "rescore_device_ms", "query_gap_ms",
    "interval_gap_ms", "query_queue_ms"])
def test_reader_silent_without_spans_or_scopes(metric):
    """A program without the stage scopes and session spans (an older
    commit) leaves each metric out; a run without a trace too."""
    bare = PROFILE.replace(SCOPE, "jit(chunk_local)/")
    for name in ("ServeSession.run", "ServeSession.take",
                 "ServeSession.query_batch"):
        bare = bare.replace(f'name: "{name}', 'name: "bench.call')
    assert SP.reader(metric)(SimpleNamespace(trace=_trace(bare),
                                             traced_calls=2)) is None
    assert SP.reader(metric)(SimpleNamespace(trace=None,
                                             traced_calls=0)) is None
