"""The trace reduction and the byte counts, on a small recorded profile
whose answers are known by hand."""
import numpy as np
import pytest

from perfbench import kernel_bytes as KB
from perfbench import trace as TR
from perfbench.tests.tiny import _read, CONFIGS
import os

PROFILE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.call" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 1500000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000
             stats { metadata_id: 1 str_value: "jit(chunk_local)/while/kernel/select_harvest.pallas/max" } }
    events { metadata_id: 4 offset_ps: 10500000 duration_ps: 1500000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 5 offset_ps: 1000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "copy.2" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.3" } }
  event_metadata { key: 4 value { id: 4 name: "all-to-all.4" } }
  event_metadata { key: 5 value { id: 5 name: "jit_chunk_local(7)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 9000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 4 value { id: 4 name: "all-to-all.4" } }
}
planes {
  id: 4 name: "/device:TPU:2"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
"""


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    return TR.from_profile(ProfileData.from_text_proto(PROFILE), n_devices=2)


def test_window_and_idle_union(trace):
    assert trace.window_s == pytest.approx(10e-6)
    # device 0: [1,3] u [5,6] u [10.5,11] us = 3.5 us (the op from 0 and
    # the op past 11 are clipped to the window); device 1: 4 + 1 us;
    # device 2 is not among the cell's two devices
    assert trace.busy_s == pytest.approx((3.5e-6 + 5e-6) / 2)


def test_scopes_and_modules(trace):
    assert trace.scope_s("kernel/select_harvest.") == pytest.approx(1e-6 / 2)
    assert trace.scope_s("kernel/bloom.") == 0.0
    assert trace.module_s("chunk_local") == pytest.approx(5e-6 / 2)
    assert trace.module_count("chunk_local") == pytest.approx(0.5)


def test_breakdown_names_gaps_by_host_span(trace):
    b = trace.breakdown
    ops = dict(b["device_ops"])
    # fusion.1 on device 0 (1 us in the window) and device 1 (4 us)
    assert b["device_ops"][0][0] == "fusion.1"
    assert ops["fusion.1"] == pytest.approx(5e-6 / 2)
    assert ops["fusion.3 (kernel/select_harvest.pallas)"] == \
        pytest.approx(1e-6 / 2)
    gaps = dict((round(s * 1e9), n) for n, s in b["idle_gaps"])
    assert gaps == {4500: "bench.call", 2000: "bench.call"}


def test_self_time_leaves_out_nested_events():
    iv = np.array([[0, 10], [2, 3], [4, 6], [12, 15]], float)
    assert TR.self_ns(iv).tolist() == [7, 1, 2, 3]
    assert TR.short_name("%while.3 = (s32[]) while(...)") == "while.3"
    assert TR.kernel_of("jit(f)/kernel/bloom.ref/gather") == "bloom.ref"
    assert TR.kernel_of("jit(f)/add") == ""


def test_union_of_nested_and_disjoint_intervals():
    iv = np.array([[0, 10], [2, 3], [12, 15], [14, 20], [30, 31]], float)
    assert TR.union_ns(iv) == 10 + 8 + 1
    assert TR.union_ns(np.zeros((0, 2))) == 0.0


def _bytes(metric, crawl, chips):
    import importlib.util
    from perfbench import spec as SP
    spec = importlib.util.spec_from_file_location(
        metric, os.path.join(SP.HERE, "metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.chunk_bytes(crawl, chips)


def test_bytes_at_deployment_a():
    crawl = _read(os.path.join(CONFIGS, "webparf-A.json"))["crawl"]
    assert KB.shapes(crawl, 1) == dict(R=512, C=4096, k=1, M=4096, steps=4)
    assert _bytes("select_harvest_roofline", crawl, 1) == 4 * 512 * 4096 * 5
    assert _bytes("dedup_deposit_roofline", crawl, 1) == 2 * 512 * 4096 * 5
    assert _bytes("opic_update_roofline", crawl, 1) == \
        (2 * 4 * 512 + 512 * 4096) * 9
    # four chips of 1024 domains: each holds A's rows and arrivals
    four = dict(crawl, n_domains=1024)
    assert KB.shapes(four, 4) == dict(R=512, C=4096, k=1, M=4096, steps=4)


def test_roofline_reader(trace):
    from perfbench import spec as SP
    from types import SimpleNamespace
    crawl = _read(os.path.join(CONFIGS, "webparf-A.json"))["crawl"]
    rec = SimpleNamespace(trace=trace, traced_calls=2, crawl_cfg=crawl,
                          chips=1, device_kind="TPU v5 lite")
    got = SP.reader("select_harvest_roofline")(rec)
    want = 100 * 2 * 4 * 512 * 4096 * 5 / 819e9 / 0.5e-6
    assert got == pytest.approx(want)
    assert SP.reader("dedup_deposit_roofline")(rec) is None


def test_scope_from_event_metadata():
    """On the TPU an operation's named scope is a stat of its metadata."""
    from jax.profiler import ProfileData
    text = PROFILE.replace(
        'event_metadata { key: 1 value { id: 1 name: "fusion.1" } }\n'
        '  event_metadata { key: 2 value { id: 2 name: "copy.2" } }',
        'event_metadata { key: 1 value { id: 1 name: "fusion.1" stats { '
        'metadata_id: 2 str_value: "jit(chunk_local)/kernel/bloom.ref/x" } '
        'stats { metadata_id: 3 int64_value: 77 } } }\n'
        '  event_metadata { key: 2 value { id: 2 name: "copy.2" } }\n'
        '  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }\n'
        '  stat_metadata { key: 3 value { id: 3 name: "flops" } }', 1)
    raw = ProfileData.text_proto_to_serialized_xspace(text)
    meta = TR.event_metadata(raw)
    assert meta["/device:TPU:0"]["fusion.1"] == {
        "tf_op": "jit(chunk_local)/kernel/bloom.ref/x", "flops": 77}
    t = TR.from_profile(ProfileData.from_serialized_xspace(raw), 2, meta)
    # device 0's fusion.1 is clipped to [1, 2] us; device 1's fusion.1 has
    # no such metadata in its own plane
    assert t.scope_s("kernel/bloom.") == pytest.approx(1e-6 / 2)


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tiny_crawl.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Two traced chunks of the tiny crawl configuration (16 domains) on
    one TPU v5 lite, with the kernel scopes on: the profile as the
    profiler wrote it, gzipped."""
    import gzip
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    return TR.reduce(str(path), n_devices=1)


def test_recorded_tpu_profile(recorded):
    t = recorded
    assert t.window_s == pytest.approx(0.011387239)
    assert t.module_count("chunk_local") == 2
    # the busy union, counted again on a nanosecond grid
    d = t.devices[0]
    grid = np.zeros(int(t.t1 - t.t0) + 1, bool)
    for s, e in d.ops:
        grid[int(s - t.t0):int(e - t.t0)] = True
    assert t.busy_s == pytest.approx(grid.sum() / 1e9, rel=1e-3)
    assert t.busy_s <= t.module_s("chunk_local") + 1e-9 <= t.window_s
    # every kernel family on the chunk's path is found by its scope, and
    # a scope's time is within the busy time
    scopes = {k: t.scope_s(f"kernel/{k}.") for k in
              ("select_harvest", "opic_update", "dedup_deposit", "bloom")}
    assert scopes == pytest.approx({"select_harvest": 1.4311e-05,
                                    "opic_update": 4.8705e-05,
                                    "dedup_deposit": 0.000211975,
                                    "bloom": 0.0})
    assert sum(scopes.values()) <= t.busy_s
    # self times of nested events add up to the busy time
    assert TR.self_ns(d.ops).sum() / 1e9 == pytest.approx(t.busy_s, rel=0.02)
    b = t.breakdown
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert all(name == "bench.call" for name, _ in b["idle_gaps"][:3])
