"""ServeSession — live crawl -> index -> serve under one mesh.

The paper's Figure 1 casts the partitioned crawl as the feeder of an
index -> search cascade; BUbiNG's framing (PAPERS.md) is that a crawler is
one component of a search engine and must be engineered against the serving
load it feeds. ``ServeSession`` is the driver that closes that loop as ONE
pipeline (DESIGN.md §16), a sibling of :class:`repro.api.CrawlSession`
built ON it (composition, per the §11 layering — drivers extend the session
API, they don't hand-roll step loops):

  per dispatch interval:
    1. ``CrawlSession.run_chunk()`` advances the crawl one fused interval
       (the jitted scan — the chunk cannot be preempted);
    2. queries that ARRIVED during that window (open-loop schedule,
       repro/serve/load.py) are answered from the index as of the previous
       fold — the batched, jitted query path (repro/serve/query.py) runs on
       the same mesh, interleaved with the crawl steps;
    3. the interval's fetched pages stream into the sharded index
       incrementally (device FetchReport -> shard-local ``add_batch``; no
       post-hoc harvest pass).

  The serve-then-fold order is the honest one: a query arriving mid-chunk
  physically cannot see that chunk's pages, so freshness lag is bounded
  below by one interval — ``index_every`` widens the fold period and the
  measured lag with it.

``run`` returns a typed :class:`repro.serve.report.ServeReport` (latency
percentiles, QPS, freshness lag, recall@k vs the full-index oracle) with
the embedded ``CrawlReport``. ``checkpoint``/``restore`` persist the index
leaves + serve cursors next to the crawl state, so a restored session
resumes serving where it left off (same schedule position, same index,
bit-identical answers — test-enforced).
"""
from __future__ import annotations

import os
import time
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.api.report import (CrawlReport, harvest, stats_dict,
                              stats_per_shard)
from repro.api.session import CrawlSession
from repro.configs.base import CrawlConfig
from repro.serve import query as Q
from repro.serve.load import QueryBatch, QueryLoad
from repro.serve.report import ServeReport

_SERVE_DIR = "serve"        # index + cursors live next to the crawl ckpt


class ServeSession:
    """Owns a CrawlSession, the sharded live index, and the query loop."""

    def __init__(self, cfg: CrawlConfig, mesh=None, *,
                 load: Optional[QueryLoad] = None, qps: float = 4.0,
                 load_seed: int = 0, index_capacity: int = 4096,
                 doc_len: int = 64, vocab: int = 4096, top_k: int = 10,
                 n_query_terms: int = 8, query_batch: int = 16,
                 index_every: int = 1, **crawl_kw):
        """``load`` overrides the default generator (``qps``/``load_seed``
        then unused). ``index_capacity`` is GLOBAL (split evenly over
        shards). ``index_every`` folds pages into the index every N
        intervals (freshness lag scales with it). Extra kwargs thread to
        :class:`CrawlSession` (extra_stages, score_fn, ...)."""
        self.crawl = CrawlSession(cfg, mesh, **crawl_kw)
        self.cfg = cfg
        self.n_shards = self.crawl.n_shards
        # one timeline: serve spans land on the crawl session's tracer
        self.telemetry = self.crawl.telemetry
        self.tracer = self.crawl.tracer
        if index_capacity % self.n_shards:
            raise ValueError(f"index_capacity={index_capacity} must divide "
                             f"over {self.n_shards} shards")
        self.cap_shard = index_capacity // self.n_shards
        if self.cap_shard < top_k:
            raise ValueError(f"per-shard capacity {self.cap_shard} < "
                             f"top_k {top_k}")
        self.doc_len, self.vocab = int(doc_len), int(vocab)
        self.top_k, self.n_query_terms = int(top_k), int(n_query_terms)
        self.query_batch = int(query_batch)
        self.index_every = max(int(index_every), 1)
        self.load = load if load is not None else QueryLoad(
            cfg, qps=qps, seed=load_seed)
        self.index = Q.init_sharded_index(self.n_shards, self.cap_shard,
                                          self.doc_len, self.vocab)
        self._add_fn = Q.make_index_add(cfg, self.crawl.mesh, self.crawl.axes)
        self._query_fn = Q.make_query_fn(cfg, self.crawl.mesh,
                                         self.crawl.axes,
                                         n_terms=self.n_query_terms,
                                         k=self.top_k)
        self._watermark = 0        # newest crawl step folded into the index
        self._q_cursor = 0         # load-schedule position consumed
        self._pending: List = []   # device reports awaiting a fold
        self._all_urls: List[np.ndarray] = []   # full page stream (oracle)

    # -- introspection ------------------------------------------------------

    @property
    def t(self) -> int:
        return self.crawl.t

    @property
    def watermark(self) -> int:
        """Crawl step of the newest indexed page (freshness anchor)."""
        return self._watermark

    @property
    def stats(self) -> Dict[str, int]:
        return self.crawl.stats

    def index_stats(self) -> Dict[str, int]:
        """Host-side index counters (one transfer of two small leaves)."""
        return dict(
            index_docs=int(np.asarray(self.index.n_docs).sum()),
            index_dropped=int(np.asarray(self.index.n_dropped).sum()),
            index_capacity=self.cap_shard * self.n_shards,
        )

    # -- the serve loop -----------------------------------------------------

    def run(self, steps: int, *, recall: bool = True,
            collect: str = "urls") -> ServeReport:
        """Drive ``steps`` crawl cycles with interleaved serving.

        ``steps`` must be a multiple of ``dispatch_interval`` (the crawl
        advances in fused chunks). ``recall=False`` skips the full-index
        oracle pass (pure-throughput benchmarking)."""
        iv = self.cfg.dispatch_interval
        if steps % iv or self.crawl.t % iv:
            raise ValueError(
                f"run: steps={steps} and t={self.crawl.t} must be multiples "
                f"of dispatch_interval={iv} (chunked execution)")
        lat, arr, lags = [], [], []
        top_u, top_s = [], []
        q_dom, q_seed = [], []
        url_parts: List[np.ndarray] = []
        per_step: List[int] = []
        crawl_secs = serve_secs = 0.0
        led0 = len(self.crawl.ledger) if self.telemetry else 0
        span = self.tracer.span
        run_w0 = time.perf_counter()

        with span("ServeSession.run", "serve", steps=steps):
            for i in range(steps // iv):
                t_start = self.crawl.t
                w0 = time.perf_counter()
                with span("ServeSession.chunk", "serve", interval=i):
                    reps = self.crawl.run_chunk()
                    jax.block_until_ready(reps)
                w1 = time.perf_counter()
                crawl_secs += w1 - w0
                t_now = self.crawl.t

                # 2. answer the interval's arrivals from the live (lagging)
                # index
                with span("ServeSession.take", "serve", interval=i) as args:
                    qb = self.load.take(self._q_cursor, float(t_now))
                    args["queries"] = len(qb)
                self._q_cursor = qb.cursor
                if len(qb):
                    serve_secs += self._serve(qb, t_start, t_now, w0, w1,
                                              lat, arr, lags, top_u, top_s)
                    q_dom.append(qb.domain)
                    q_seed.append(qb.seed)

                # 3. stream the chunk's pages into the index (incremental
                # fold)
                self._pending.append(reps)
                if len(self._pending) >= self.index_every:
                    self._flush_pending()
                with span("ServeSession.harvest", "serve", interval=i):
                    u, c = harvest(reps)
                    per_step.extend(c)
                    self._all_urls.extend(u)
                    if collect == "urls":
                        url_parts.extend(u)

            seconds = time.perf_counter() - run_w0
            with span("ServeSession.report", "serve"):
                crawl_tel = self.crawl.telemetry_report(start=led0)
                crawl_rep = CrawlReport(
                    urls=(np.concatenate(url_parts) if url_parts
                          else np.array([], np.uint32)),
                    per_step=np.asarray(per_step, np.int64),
                    stats=stats_dict(self.crawl.state), seconds=crawl_secs,
                    cfg=self.cfg,
                    stats_per_shard=stats_per_shard(self.crawl.state),
                    telemetry=crawl_tel)
                top_u_a = (np.concatenate(top_u) if top_u
                           else np.zeros((0, self.top_k), np.uint32))
                top_s_a = (np.concatenate(top_s) if top_s
                           else np.zeros((0, self.top_k), np.float32))
                rec = None
                if recall and len(top_u_a) and self._all_urls:
                    rec = self._oracle_recall(
                        np.concatenate(q_seed), np.concatenate(q_dom),
                        top_u_a)
                lat_a = np.asarray(lat, np.float64)
                lags_a = np.asarray(lags, np.int64)
                serve_tel = None
                if crawl_tel is not None:
                    from repro.obs.health import ServeTelemetry
                    serve_tel = ServeTelemetry(crawl=crawl_tel,
                                               lag_steps=lags_a,
                                               latency_ms=lat_a)
                return ServeReport(
                    crawl=crawl_rep, latency_ms=lat_a,
                    arrival_step=np.asarray(arr, np.float64),
                    lag_steps=lags_a,
                    top_urls=top_u_a, top_scores=top_s_a, k=self.top_k,
                    seconds=seconds, serve_seconds=serve_secs,
                    index=self.index_stats(), recall_at_k=rec, cfg=self.cfg,
                    telemetry=serve_tel)

    def _serve(self, qb: QueryBatch, t_start: int, t_now: int,
               w0: float, w1: float, lat, arr, lags, top_u, top_s) -> float:
        """Run one interval's arrivals through the batched query path."""
        B = self.query_batch
        lag = t_now - self._watermark
        # map step-time arrivals into the interval's wall window: queries
        # arrived WHILE the chunk crawled, so they queue behind it
        frac = (qb.time - t_start) / max(t_now - t_start, 1)
        arrival_wall = w0 + np.clip(frac, 0.0, 1.0) * (w1 - w0)
        spent = 0.0
        for lo in range(0, len(qb), B):
            n = min(B, len(qb) - lo)
            with self.tracer.span("ServeSession.query_batch", "serve", n=n,
                                  batch=lo // B, lag_steps=lag):
                seeds = np.zeros((B,), np.uint32)
                doms = np.zeros((B,), np.int32)
                seeds[:n] = qb.seed[lo:lo + n]
                doms[:n] = qb.domain[lo:lo + n]
                b0 = time.perf_counter()
                s, u = self._query_fn(self.index, jnp.asarray(seeds),
                                      jnp.asarray(doms))
                jax.block_until_ready((s, u))
                done = time.perf_counter()
                # slice on the host: a device slice compiles once per n
                top_u.append(np.asarray(u, np.uint32)[:n])
                top_s.append(np.asarray(s, np.float32)[:n])
            spent += done - b0
            lat.extend((done - arrival_wall[lo:lo + n]) * 1e3)
            arr.extend(qb.time[lo:lo + n])
            lags.extend([lag] * n)
        return spent

    def _flush_pending(self) -> None:
        if self._pending:
            with self.tracer.span("ServeSession.fold", "serve",
                                  n_intervals=len(self._pending)):
                for rep in self._pending:
                    self.index = self._add_fn(self.index, rep)
                if self.telemetry:
                    jax.block_until_ready(self.index)
        self._pending = []
        self._watermark = self.crawl.t

    def _oracle_recall(self, seeds: np.ndarray, doms: np.ndarray,
                       served: np.ndarray) -> float:
        pages = np.concatenate(self._all_urls)
        oracle = Q.oracle_index(pages, self.cfg, doc_len=self.doc_len,
                                vocab=self.vocab)
        want = Q.oracle_search(oracle, seeds, doms,
                               n_terms=self.n_query_terms, k=self.top_k,
                               cfg=self.cfg)
        return Q.recall_at_k(served, want)

    # -- one-off queries (examples / smoke checks) --------------------------

    def answer(self, domains, seeds=None):
        """Answer ad-hoc queries against the live index: ``(scores, urls)``
        as (n, k) numpy. ``seeds`` defaults to the domain ids."""
        domains = np.atleast_1d(np.asarray(domains, np.int32))
        seeds = (domains.astype(np.uint32) + 1 if seeds is None
                 else np.atleast_1d(np.asarray(seeds, np.uint32)))
        B = self.query_batch
        out_s, out_u = [], []
        for lo in range(0, len(domains), B):
            sd = np.zeros((B,), np.uint32)
            dm = np.zeros((B,), np.int32)
            n = min(B, len(domains) - lo)
            sd[:n] = seeds[lo:lo + n]
            dm[:n] = domains[lo:lo + n]
            s, u = self._query_fn(self.index, jnp.asarray(sd),
                                  jnp.asarray(dm))
            out_s.append(np.asarray(s)[:n])
            out_u.append(np.asarray(u)[:n])
        return np.concatenate(out_s), np.concatenate(out_u)

    # -- C4 fault controls (proxied: serving survives crawl-shard death) ----

    def inject_failure(self, shards) -> "ServeSession":
        self.crawl.inject_failure(shards)
        return self

    def heal(self, shards=None) -> "ServeSession":
        self.crawl.heal(shards)
        return self

    # -- persistence --------------------------------------------------------

    def _serve_tree(self):
        return {"index": self.index,
                "watermark": jnp.asarray(self._watermark, jnp.int32),
                "q_cursor": jnp.asarray(self._q_cursor, jnp.int32)}

    def checkpoint(self, ckpt_dir: str, *, keep: int = 3) -> str:
        """Write crawl state + index leaves + serve cursors atomically.
        Pending (unfolded) intervals are folded first so the on-disk index
        matches the watermark."""
        from repro.train import checkpoint as ckpt
        self._flush_pending()
        path = self.crawl.checkpoint(ckpt_dir, keep=keep)
        ckpt.save(os.path.join(ckpt_dir, _SERVE_DIR), self.crawl.t,
                  self._serve_tree(), keep=keep)
        return path

    def restore(self, ckpt_dir: str, *, step: Optional[int] = None
                ) -> "ServeSession":
        """Restore crawl + index + schedule cursor; serving resumes exactly
        where the checkpoint left off."""
        from repro.train import checkpoint as ckpt
        self.crawl.restore(ckpt_dir, step=step)
        tree = ckpt.restore(os.path.join(ckpt_dir, _SERVE_DIR),
                            self._serve_tree(), step=self.crawl.t)
        self.index = tree["index"]
        self._watermark = int(np.asarray(tree["watermark"]))
        self._q_cursor = int(np.asarray(tree["q_cursor"]))
        self._pending = []
        self._all_urls = []        # oracle stream restarts at the restore
        return self
