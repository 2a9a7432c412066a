"""One run of one cell: set up, measure a window, check, report.

    python3 perfbench/run.py --workload A.crawl --seed 7 --seconds 20 --trace 0

Set-up builds the program's session for the cell's configuration on the
first ``chips`` devices, fills what the traffic needs (a pre-filled index
for search traffic) and runs two dispatch intervals, which compile every
program the window uses. The window then calls the program once per
interval until ``--seconds`` have passed. Crawl traffic starts each fused
interval with ``CrawlSession.run_chunk`` and keeps up to the mix's
``in_flight`` intervals queued on the device, reading each one's fetched
pages once it has run, so that a host that stands still leaves the chip
fed; at the close it sends nothing more, waits for all that was sent, and
reads the clock after that wait. Search traffic calls ``ServeSession.run``,
which waits for its own interval. With ``--trace 1`` the profiler records
the first few calls of the window; the per-layer metrics are reduced from
that trace. After the window, every page fetched since the start and every
checked query answer is compared with the plain references (``crawlref``,
``searchref``).
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import gc
import glob
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

from perfbench import spec as SP

TRACE_DIR = os.path.join(SP.ROOT, "perfbench_out", "trace")
WARM_CALLS = 2          # the fused chunk compiles on its first two calls


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


@dataclass
class Record:
    """What a run measured; each metric reader reduces it to one number."""
    cell: SP.Cell
    crawl_cfg: dict
    chips: int
    device_kind: str = ""
    setup_s: float = 0.0
    window_s: float = 0.0
    pages: int = 0
    calls: int = 0
    queries_answered: int = 0
    latency_ms: np.ndarray = field(default_factory=lambda: np.empty(0))
    age_s: np.ndarray = field(default_factory=lambda: np.empty(0))
    due_in_window: int = 0
    call_s: List[float] = field(default_factory=list)   # between results
    call_queries: List[int] = field(default_factory=list)
    trace: object = None          # trace.Trace of the traced calls, or None
    traced_calls: int = 0
    traced_query_batches: int = 0
    traced_folds: int = 0


class CrawlDriver:
    """Crawl traffic: the session fetches, nothing queries it."""

    def __init__(self, cell: SP.Cell, cfg, mesh, seed: int):
        self.cell, self.cfg, self.mesh, self.seed = cell, cfg, mesh, seed
        self.iv = cfg.dispatch_interval
        self.steps: List[np.ndarray] = []     # fetched URLs of every step
        self.accuracy = float(cell.config["classify_accuracy"])

    def open(self):
        from repro.api import CrawlSession
        self.sess = CrawlSession(self.cfg, self.mesh,
                                 classify_accuracy=self.accuracy)
        return self.sess

    def setup(self) -> None:
        self.open()
        for _ in range(WARM_CALLS):
            self.call()

    def _log_steps(self, urls: np.ndarray, per_step: np.ndarray) -> None:
        off = 0
        for c in per_step:
            self.steps.append(urls[off:off + c])
            off += c

    def dispatch(self):
        """Start one fused dispatch interval; nothing waits for it."""
        return self.sess.run_chunk()

    def collect(self, reps) -> Dict[str, int]:
        """Wait for one dispatched interval and log its fetched pages."""
        from repro.api.report import harvest
        urls, counts = harvest(reps)
        self.steps.extend(urls)
        return dict(pages=int(sum(counts)), queries=0, batches=0, folds=0)

    def call(self) -> Dict[str, int]:
        return self.collect(self.dispatch())

    def crawl_state(self):
        return self.sess.state

    def finish(self, t_end: float) -> None:
        pass

    def close(self) -> None:
        del self.sess


class ServeDriver(CrawlDriver):
    """Search traffic: open-loop queries on the wall clock against the live
    index while the crawl runs; the index starts pre-filled."""

    def __init__(self, cell, cfg, mesh, seed):
        super().__init__(cell, cfg, mesh, seed)
        s = cell.traffic["search"]
        self.s = s
        self.cap = int(s["index_prefill"]) + int(s["index_room"])
        self.answers = []   # (take wall, lo, hi, latency_ms, urls, scores,
                            #  docs visible, previous take wall)
        self.take_walls: List[float] = []
        self.docs = 0

    def open(self):
        import jax
        from repro.serve import ServeSession
        from perfbench.loadgen import WallLoad
        s = self.s
        self.load = WallLoad(rate=float(s["rate_qps"]), n_domains=self.cfg.n_domains,
                             seed=self.seed, zipf_q=s["zipf_q"],
                             block_s=s["count_block_s"])
        self.sess = ServeSession(
            self.cfg, self.mesh, load=self.load, index_capacity=self.cap,
            doc_len=s["doc_len"], vocab=s["vocab"], top_k=s["top_k"],
            n_query_terms=s["terms"], query_batch=s["batch"],
            index_every=s["index_every"], classify_accuracy=self.accuracy)
        self.prefill_urls = prefill_urls(
            self.seed, int(s["index_prefill"]), self.cfg)
        add = _index_add(self.cfg, self.sess)
        for part in self.prefill_urls:
            self.sess.index = add(self.sess.index, part)
        jax.block_until_ready(self.sess.index)
        self.docs = int(s["index_prefill"])
        return self.sess

    def setup(self) -> None:
        self.open()
        for _ in range(WARM_CALLS):
            self.load.add_warm(self.s["batch"], seed_base=len(self.take_walls))
            self.call()
        backlog = float(self.s["warm_backlog_s"])
        self.load.start(time.perf_counter() - backlog)

    def call(self) -> Dict[str, int]:
        n_takes = len(self.load.takes)
        visible = min(self.docs, self.cap)
        rep = self.sess.run(self.iv, recall=False)
        self._log_steps(rep.crawl.urls, rep.crawl.per_step)
        prev = self.take_walls[-1] if self.take_walls else None
        if len(self.load.takes) > n_takes:
            wall, lo, hi = self.load.takes[-1]
            self.answers.append((wall, lo, hi, rep.latency_ms.copy(),
                                 rep.top_urls.copy(), rep.top_scores.copy(),
                                 visible, prev))
        else:
            wall = time.perf_counter()     # a warm call's take (no record)
        self.take_walls.append(wall)
        self.docs += int(rep.crawl.fetched)
        n = int(rep.n_queries)
        b = self.s["batch"]
        return dict(pages=int(rep.crawl.fetched), queries=n,
                    batches=-(-n // b), folds=1)

    def dispatch(self) -> Dict[str, int]:
        return self.call()         # ServeSession.run waits for its interval

    def collect(self, got: Dict[str, int]) -> Dict[str, int]:
        return got

    def crawl_state(self):
        return self.sess.crawl.state

    def finish(self, t_end: float) -> None:
        """Answer every query due by the window's close (late, not lost)."""
        for _ in range(8):
            if self.answers and self.answers[-1][0] >= t_end:
                break
            self.call()


def prefill_urls(seed: int, n: int, cfg):
    """Pages crawled before the run: ``n`` URLs of the web, their domains
    drawn by the web's own Zipf skew, made on the device from ``seed`` and
    shaped as the program's fetch reports (steps, rows, lanes)."""
    import jax
    import jax.numpy as jnp
    from repro.core.stages import FetchReport
    per_add = min(n, 1 << 20)
    rows = cfg.n_slots
    lanes = 128
    steps = per_add // (rows * lanes)
    w = 1.0 / np.arange(1, cfg.n_domains + 1) ** cfg.zipf_a
    cumw = jnp.asarray(np.cumsum(w / w.sum()), jnp.float32)
    local_bits = cfg.url_space_log2 - int(np.log2(cfg.n_domains))
    from perfbench.searchref import hash2

    @jax.jit
    def make(part, key):
        i = (jnp.arange(per_add, dtype=jnp.uint32)
             + part.astype(jnp.uint32) * jnp.uint32(per_add))
        h_dom = hash2(i, key, 41)
        dom = jnp.searchsorted(
            cumw, h_dom.astype(jnp.float32) * jnp.float32(2.0 ** -32))
        dom = jnp.minimum(dom, cfg.n_domains - 1).astype(jnp.uint32)
        local = hash2(i, key, 43) & jnp.uint32((1 << local_bits) - 1)
        url = (dom << local_bits) | local
        url = jnp.where(url == 0, jnp.uint32(1), url)
        return FetchReport(url.reshape(steps, rows, lanes),
                           jnp.ones((steps, rows, lanes), bool))

    if n % per_add or per_add % (rows * lanes):
        raise ValueError(f"index_prefill {n} must be whole fetch reports of "
                         f"{rows} rows x {lanes} lanes")
    key = jnp.uint32((seed ^ (seed >> 32)) & 0xFFFFFFFF)
    return [make(jnp.int32(p), key) for p in range(n // per_add)]


def _index_add(cfg, sess):
    from repro.serve.query import make_index_add
    return make_index_add(cfg, sess.crawl.mesh, sess.crawl.axes)


def _driver(cell, cfg, mesh, seed):
    return (ServeDriver if "search" in cell.traffic else CrawlDriver)(
        cell, cfg, mesh, seed)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run_cell(cell: SP.Cell, *, seed: int, seconds: float, trace: bool,
             devices, t_process: float, tamper=None) -> dict:
    """Set up, measure, check. ``tamper(driver)``, for the tests and the
    control only, returns a context manager that plants a change in the
    program for the whole run."""
    import jax
    from repro.configs.base import CrawlConfig
    from repro.kernels import registry
    from repro.launch.mesh import make_host_mesh
    registry.set_annotations(True)        # named kernel scopes: metadata only
    cfg = CrawlConfig(**cell.config["crawl"])
    mesh = make_host_mesh(devices=devices)
    drv = _driver(cell, cfg, mesh, seed)
    rec = Record(cell=cell, crawl_cfg=cell.config["crawl"],
                 chips=len(devices), device_kind=devices[0].device_kind)
    with (tamper(drv) if tamper else contextlib.nullcontext()):
        drv.setup()
        jax.block_until_ready(drv.crawl_state())
        n_warm_steps = len(drv.steps)
        t_open = time.perf_counter()
        rec.setup_s = t_open - t_process
        log(f"set-up {rec.setup_s:.3f} s")
        trace_calls = int(cell.traffic["trace_calls"]) if trace else 0
        in_flight = int(cell.traffic.get("in_flight", 1))
        if trace_calls:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(TRACE_DIR)
        win = contextlib.ExitStack()
        if trace_calls:
            win.enter_context(jax.profiler.TraceAnnotation("bench.window"))
        pending = collections.deque()
        sent, closing, t_last = 0, False, t_open
        while pending or not closing:
            room = 0 if closing else in_flight - len(pending)
            if rec.calls < trace_calls:        # no call past the traced ones
                room = min(room, trace_calls - sent)
            for _ in range(room):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    pending.append(drv.dispatch())
                sent += 1
            with jax.profiler.TraceAnnotation("bench.call"):
                got = drv.collect(pending.popleft())
            t_end = time.perf_counter()
            rec.call_s.append(t_end - t_last)
            t_last = t_end
            rec.call_queries.append(got["queries"])
            rec.calls += 1
            rec.pages += got["pages"]
            rec.queries_answered += got["queries"]
            if rec.calls <= trace_calls:
                rec.traced_query_batches += got["batches"]
                rec.traced_folds += got["folds"]
                if rec.calls == trace_calls:
                    win.close()
                    jax.profiler.stop_trace()
                    rec.traced_calls = trace_calls
            if t_end - t_open >= seconds:
                closing = True             # send nothing more; wait for all
        if rec.calls < trace_calls:
            win.close()
            jax.profiler.stop_trace()
            rec.traced_calls = rec.calls
        rec.window_s = t_end - t_open
        window_steps = len(drv.steps) - n_warm_steps
        drv.finish(t_end)
        jax.block_until_ready(drv.crawl_state())
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    log(f"window {rec.window_s:.3f} s, {rec.calls} calls, {window_steps} "
        f"steps, {rec.pages} pages, peak {peak} B")
    st = drv.crawl_state()
    final = dict(f_url=np.asarray(st.f_url), f_valid=np.asarray(st.f_valid),
                 f_pri=np.asarray(st.f_pri),
                 order_state=np.asarray(st.order_state))
    bloom = st.bloom_bits          # compared where it lies, then freed
    steps, search, checks = drv.steps, None, {}
    if isinstance(drv, ServeDriver):
        _query_times(drv, rec, t_open, t_end)
        search = dict(answers=drv.answers, prefill=drv.prefill_urls,
                      load=drv.load)
        checks["unanswered"] = float(drv.unanswered)
    drv.close()
    del drv, st
    gc.collect()
    if trace_calls:
        from perfbench import trace as TR
        rec.trace = TR.reduce(_trace_file(), n_devices=len(devices))
    from perfbench import checks as CK
    checks.update(CK.check_crawl(cell, len(devices), steps, final, bloom))
    del bloom
    gc.collect()
    if search is not None:
        checks.update(CK.check_search(cell, seed, steps, **search))
    return dict(rec=rec, checks=checks, peak=peak)


def _trace_file() -> str:
    files = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {TRACE_DIR}")
    return max(files, key=os.path.getmtime)


def _query_times(drv: ServeDriver, rec: Record, t_open: float,
                 t_end: float) -> None:
    """Latency from each query's wall due time to its answer, and the age
    of the index that answered it, over every query due in the window."""
    lat, age = [], []
    answered = 0
    for wall, lo, hi, lat_ms, _, _, _, prev in drv.answers:
        due = drv.load.due(lo, hi)
        answer = wall + lat_ms / 1e3       # the session times from the take
        inside = (due >= t_open) & (due <= t_end)
        answered += int(inside.sum())
        lat.append(((answer - due) * 1e3)[inside])
        age.append((answer - prev)[inside])
    rec.latency_ms = np.concatenate(lat) if lat else np.empty(0)
    rec.age_s = np.concatenate(age) if age else np.empty(0)
    rec.due_in_window = (drv.load.n_due(t_end)
                         - drv.load.n_due(t_open, inclusive=False))
    drv.unanswered = rec.due_in_window - answered


# ---------------------------------------------------------------------------
# the command
# ---------------------------------------------------------------------------

def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def accelerator(chips: int):
    """The first ``chips`` TPU devices, or None (and why) when JAX has none."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return None, f"JAX found no TPU (platform {devs[0].platform!r})"
    if len(devs) < chips:
        return None, f"the cell needs {chips} chips, JAX sees {len(devs)}"
    return devs[:chips], ""


def result(cell: SP.Cell, out: dict, devices, trace: bool) -> dict:
    """The result line: metrics by name, the device, and the checks last."""
    rec, checks = out["rec"], out["checks"]
    limits = cell.limits
    missing = sorted(set(checks) - set(limits))
    if missing:
        raise KeyError(f"no limit for the compared numbers {missing}")
    correct = all(checks[n] <= limits[n] for n in checks)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = SP.reader(m.name)(rec)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(out["peak"])}
    line = {"correct": bool(correct),
            "attempted": int(rec.pages + rec.due_in_window),
            "failed": int(checks.get("unanswered", 0)),
            "metrics": metrics, "device": device}
    if trace and rec.trace is not None:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        line["breakdown"] = rec.trace.breakdown
    line["checks"] = {n: {"value": checks[n], "limit": limits[n]}
                      for n in sorted(checks)}
    return line


def main(argv, t_process: float) -> int:
    args = parse(argv)
    cell = SP.load_cell(args.workload)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    devices, why = accelerator(cell.chips)
    if devices is None:
        log(f"perfbench: {why}; no result")
        return 2
    out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), devices=devices,
                   t_process=t_process)
    line = result(cell, out, devices, bool(args.trace))
    for n, c in line["checks"].items():
        log(f"check {n} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0
