"""The four-chip cell ``Ax4.crawl`` and its exchange metric.

``exchange_device_ms`` on a small hand-written profile of two devices whose
answer is worked out by hand, the cell as ``BENCHMARK.json`` names it, and
whole runs of a ``webparf-Ax4``-shaped cell cut to the CPU on four virtual
devices: correct when sound, not correct without the all_to_all.

    JAX_PLATFORMS=cpu PYTHONPATH=src:. python -m pytest -q perfbench/tests
"""
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from perfbench import kernel_bytes as KB
from perfbench import spec as SP
from perfbench import trace as TR

SCOPE = "jit(chunk_local)/stage/dispatch/"

# Window [0, 10] us. Device 0: two exchange ops [2, 4] and [3, 5] (their
# union is 3) and a rescore op [6, 8]; device 1: one exchange op [1, 2].
# Over two chunks: (3 + 1) / 2 devices / 2 chunks = 1 us a chunk.
PROFILE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 2000000
             stats { metadata_id: 1 str_value: "%(s)sexchange/scatter" } }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 2000000
             stats { metadata_id: 1 str_value: "%(s)sexchange/all_to_all" } }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 2000000
             stats { metadata_id: 1 str_value: "%(s)srescore/clip" } } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "all-to-all.1" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.3" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 1000000
             stats { metadata_id: 1 str_value: "%(s)sexchange/all_to_all" } } }
  event_metadata { key: 1 value { id: 1 name: "all-to-all.1" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
""" % {"s": SCOPE}


def _rec(text=PROFILE):
    from jax.profiler import ProfileData
    return SimpleNamespace(trace=TR.from_profile(
        ProfileData.from_text_proto(text), n_devices=2), traced_calls=2)


def test_exchange_ms_worked_out():
    assert SP.reader("exchange_device_ms")(_rec()) == pytest.approx(1e-3)


def test_exchange_ms_silent_without_the_scope():
    """The parent program has no exchange scope: the metric is left out."""
    bare = PROFILE.replace("dispatch/exchange/", "dispatch/")
    assert SP.reader("dispatch_device_ms")(_rec(bare)) > 0
    assert SP.reader("exchange_device_ms")(_rec(bare)) is None
    assert SP.reader("exchange_device_ms")(
        SimpleNamespace(trace=None, traced_calls=0)) is None


def test_cell_is_deployment_A_on_each_of_four_chips():
    a, ax4 = SP.load_cell("A.crawl"), SP.load_cell("Ax4.crawl")
    assert (a.chips, ax4.chips) == (1, 4)
    assert ax4.traffic == a.traffic
    assert ax4.limits == a.limits
    assert KB.shapes(ax4.config["crawl"], 4) == KB.shapes(a.config["crawl"], 1)
    changed = {k for k in a.config["crawl"]
               if a.config["crawl"][k] != ax4.config["crawl"][k]}
    assert changed == {"n_domains"}
    assert [m.name for m in ax4.end_to_end] == ["pages_per_s", "setup_s"]
    layer = {m.name for m in ax4.per_layer}
    assert layer == {"device_idle_pct", "chunk_device_ms",
                     "select_harvest_roofline", "opic_update_roofline",
                     "dedup_deposit_roofline", "exchange_device_ms"}


FOUR = """
import copy, sys, time, jax
sys.path[:0] = {paths!r}
from perfbench import faults as FA, harness as H, spec as SP
from perfbench.tests.tiny import TINY_CRAWL
full = SP.load_cell("Ax4.crawl")
cfg = copy.deepcopy(full.config)
cfg["crawl"].update(TINY_CRAWL, n_domains=16 * full.chips)
cell = SP.Cell(name="tiny.Ax4", chips=full.chips, config=cfg,
               traffic=full.traffic, end_to_end=[], per_layer=[])
for name in ("", "no_exchange"):
    out = H.run_cell(cell, seed=(1 << 31) + 4321, seconds={seconds},
                     trace=False, devices=jax.devices()[:cell.chips],
                     t_process=time.perf_counter(),
                     tamper=FA.FAULTS.get(name))
    bad = sorted(n for n, v in out["checks"].items() if v > cell.limits[n])
    print("RESULT", name or "sound", ",".join(bad) or "correct",
          out["rec"].pages)
"""


def test_tiny_ax4_cell_on_four_virtual_devices():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR.format(paths=[root, os.path.join(root, "src")], seconds=1.5)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = {l.split()[1]: l.split()[2:] for l in p.stdout.splitlines()
           if l.startswith("RESULT")}
    assert got["sound"][0] == "correct", got
    assert int(got["sound"][1]) > 0, got
    assert "queue_mismatch" in got["no_exchange"][0], got
