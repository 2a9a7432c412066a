"""Shared crawl-benchmark driver — thin wrappers over ``repro.api``.

The loop itself lives in ``repro.api.CrawlSession`` now; this module keeps
the historical ``(urls, state, per_step, wall)`` tuple shape the benchmark
suites consume, and re-exports the metric helpers from their new home in
``repro.api.report``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

# the virtual-shard studies run on the host CPU backend by design: each
# child gets JAX_PLATFORMS=cpu, so none of them contends for an accelerator
# that the parent (or another process) holds
CPU_CHILD_NOTE = "virtual CPU shards in a JAX_PLATFORMS=cpu child"


def run_cpu_child(src: str, *, timeout: int = 900) -> dict:
    """Run ``python -c src`` on the CPU backend and return the JSON object
    it prints on its last stdout line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", src], capture_output=True,
                       text=True, timeout=timeout, cwd=".", env=env)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])
    return json.loads(r.stdout.strip().splitlines()[-1])


def run_crawl(cfg, steps, *, classify_accuracy=0.9, mesh=None,
              events=None, mode="auto"):
    """Drive a crawl for `steps`; returns (fetched urls, state, per-step
    fetch counts, wall seconds). `events` maps step -> callable(state)."""
    from repro.api import CrawlSession
    sess = CrawlSession(cfg, mesh, classify_accuracy=classify_accuracy)
    rep = sess.run(steps, events=events, mode=mode)
    return rep.urls, sess.state, rep.per_step, rep.seconds


def stats_dict(state):
    from repro.api import stats_dict as _stats_dict
    return _stats_dict(state)


def overlap_metrics(urls, cfg):
    from repro.api import overlap_metrics as _overlap_metrics
    return _overlap_metrics(urls, cfg)
