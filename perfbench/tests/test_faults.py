"""Whole runs of the harness on the CPU at a tiny size, with the chip check
skipped: a sound run is correct, and every control and fault the cells can
have makes ``correct`` come out false.

    JAX_PLATFORMS=cpu PYTHONPATH=src:. python -m pytest -q perfbench/tests
"""
import os
import subprocess
import sys
import time

import pytest

from perfbench import faults as FA
from perfbench import harness as H
from perfbench.tests.tiny import tiny_cell

SECONDS = 1.5


def run(traffic, tamper=None, chips=1, seed=(1 << 31) + 12345,
        seconds=SECONDS):
    import jax
    cell = tiny_cell(traffic, chips)
    out = H.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                     devices=jax.devices()[:chips],
                     t_process=time.perf_counter(), tamper=tamper)
    limits = cell.limits
    bad = {n: v for n, v in out["checks"].items() if v > limits[n]}
    return out, bad


@pytest.mark.parametrize("traffic", ["crawl", "search"])
def test_sound_run_is_correct(traffic):
    out, bad = run(traffic)
    assert not bad, bad
    assert out["rec"].pages > 0


@pytest.mark.parametrize("traffic,tamper,fails", [
    ("crawl", "hash_rows", "queue_mismatch"),
    ("crawl", "bloom_forget", "bloom_mismatch"),
    ("crawl", "state_unchanged", "invalid_fetch"),
    ("crawl", "half_batch", "budget_short"),
    ("crawl", "altered_fetch", "invalid_fetch"),
    ("crawl", "skip_rescore", "rescore_mismatch"),
    ("crawl", "pop_lowest", "pop_order"),
    ("search", "bf16_scores", "topk_gap"),
    ("search", "altered_answer", "topk_gap"),
])
def test_planted_change_is_not_correct(traffic, tamper, fails):
    fn = {**FA.CONTROLS, **FA.FAULTS}[tamper]
    _, bad = run(traffic, tamper=fn)
    assert fails in bad, bad


FOUR = """
import sys, time, jax
sys.path[:0] = {paths!r}
from perfbench import faults as FA, harness as H
from perfbench.tests.tiny import tiny_cell
cell = tiny_cell("crawl", 4)
for name in ("", "no_exchange", "bloom_forget"):
    out = H.run_cell(cell, seed=77, seconds={seconds}, trace=False,
                     devices=jax.devices()[:4], t_process=time.perf_counter(),
                     tamper=FA.FAULTS.get(name) or FA.CONTROLS.get(name))
    bad = sorted(n for n, v in out["checks"].items() if v > cell.limits[n])
    print("RESULT", name or "sound", ",".join(bad) or "correct")
"""


def test_four_shards_on_virtual_devices():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR.format(paths=[root, os.path.join(root, "src")],
                       seconds=SECONDS)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    lines = [l for l in p.stdout.splitlines() if l.startswith("RESULT")]
    assert p.returncode == 0, p.stderr[-3000:]
    got = dict(l.split()[1:3] for l in lines)
    assert got["sound"] == "correct", got
    assert "queue_mismatch" in got["no_exchange"], got
    assert got["bloom_forget"] != "correct", got
