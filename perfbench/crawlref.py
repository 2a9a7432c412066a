"""The plain reference of the partitioned crawl, replayed beside the program.

It replays the crawl step by step from the program's own fetched pages and
holds the program to the crawl's semantics:

* each fetched URL was queued in a row of the fetching shard (the frontier
  select and harvest, and C1: a URL fetched once is never queued again);
* each fetched URL was the best the queues held: no URL of a higher
  priority bucket stayed queued in its row, and, at the first step after a
  rescore, in any row of its shard the fetch budget passed over
  (``pop_order``, see ``_judge_order``);
* each shard fetched as many pages as its fetch budget and its non-empty
  rows allow (the allocator);
* the outlinks of the fetched pages are canonicalised, de-duplicated,
  staged, routed by the predicted domain, exchanged, Bloom-checked and
  queued exactly as the configuration's semantics say, so the queues at
  the end hold exactly the URLs the reference holds, and each row's Bloom
  filter has exactly the bits the reference set (dedup, dispatch and
  exchange);
* OPIC cash moves as its rules say: each fetched page spends its cell cash
  plus its row's slot cash over its outlinks, a duplicate arrival deposits
  into its queued twin's cell, and whatever is dropped is refunded to the
  slot cash of its row. The reference keeps cash in float64; the program's
  cells, slot cash and history are compared with it, and the total with
  its start (conservation);
* every dispatch's whole-queue rescore puts each queued URL in the
  priority bucket opic_url's score gives it.

FIFO stamps within a bucket, and the buckets that the allocator gives
popped-but-unfetched URLs when it returns them, are the program's
business: the order check allows for them (``_judge_order``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

from perfbench.webref import Web, hash2

BLOOM_TILE = 256        # URLs per Bloom probe-then-insert tile


@dataclass
class Replay:
    invalid: int = 0          # fetched URLs no row of the shard held
    order: int = 0            # fetched URLs a better queued URL outranked
    short: int = 0            # pages a shard's budget left unfetched
    steps: int = 0
    fetched: int = 0
    examples: List[str] = field(default_factory=list)

    def note(self, what: str) -> None:
        if len(self.examples) < 5:
            self.examples.append(what)


class CrawlReference:
    """Replays the crawl of one configuration over ``n_shards`` shards."""

    def __init__(self, cfg: dict, n_shards: int, accuracy: float):
        if cfg["partitioning"] != "webparf" or cfg["ordering"] != "opic_url" \
                or cfg["coordination"] != "exchange":
            raise ValueError("the reference replays webparf partitioning, "
                             "opic_url cash and the exchange mode")
        self.web = Web(cfg)
        self.n_shards = n_shards
        self.accuracy = float(accuracy)
        self.n_domains = int(cfg["n_domains"])
        self.n_slots = self.n_domains * int(cfg["slot_factor"])
        self.r_local = self.n_slots // n_shards
        self.capacity = int(cfg["frontier_capacity"])
        self.fetch_batch = int(cfg["fetch_batch"])
        self.k_row = max(1, self.fetch_batch // self.r_local)
        self.interval = int(cfg["dispatch_interval"])
        self.S = int(cfg["dispatch_capacity"])
        self.cap_ex = max(8, -(-self.S // n_shards) * 2)
        # arrivals a row can take per dispatch (the per-row bucket)
        self.row_bucket = min(n_shards * self.cap_ex, self.capacity)
        self.O = self.web.outlinks_per_page
        # webparf's initial layout: shard s holds its domains in its first
        # slots, the rest are spare
        per_dom = self.n_domains // n_shards
        dom = np.arange(self.n_domains)
        self.slot_of_domain = (dom // per_dom) * self.r_local + dom % per_dom
        self.bloom_hashes = int(cfg["bloom_hashes"])
        self.bloom_mask = (1 << int(cfg["bloom_bits_log2"])) - 1
        self.n_buckets = int(cfg["n_priority_buckets"])
        # each queued URL's possible priority buckets after the last rescore
        # (row -> url -> (lowest, highest)), and per row how many queued
        # URLs have each lowest bucket; None until the first rescore
        self.bounds: Optional[List[Dict[int, tuple]]] = None
        self.lo_count = np.zeros((self.n_slots, self.n_buckets), np.int64)
        self.since = 0                # steps since the last rescore
        self.rows: List[Dict[int, float]] = [dict() for _ in range(self.n_slots)]
        self.bloom: List[set] = [set() for _ in range(self.n_slots)]  # bits
        self.where: Dict[int, set] = {}
        self.slot_cash = np.zeros(self.n_slots)
        self.history = np.zeros(self.n_slots)
        self.slot_cash[self.slot_of_domain] = 1.0
        self.cash0 = float(self.n_domains)
        seeds = self.web.hub_seeds()
        for d in range(self.n_domains):
            g = int(self.slot_of_domain[d])
            for u in seeds[d]:
                u = int(u)
                if u not in self.rows[g]:
                    self._queue(g, u, 0.0)
            self.bloom[g].update(*self._bits(seeds[d]))
        self.staging = [[] for _ in range(n_shards)]   # (url, src domain, cash)
        self.t = 0
        self.result = Replay()

    # -- row bookkeeping --------------------------------------------------

    def _bits(self, urls) -> List[tuple]:
        """The Bloom bit positions of each URL (double hashing)."""
        urls = np.asarray(urls, np.uint32)
        if not len(urls):
            return []
        h1 = hash2(urls, 101)
        h2 = hash2(urls, 202) | np.uint32(1)
        i = np.arange(self.bloom_hashes, dtype=np.uint32)
        with np.errstate(over="ignore"):
            pos = (h1[:, None] + i[None, :] * h2[:, None]) \
                & np.uint32(self.bloom_mask)
        return [tuple(p) for p in pos.tolist()]

    def _queue(self, g: int, u: int, cash: float) -> None:
        self.rows[g][u] = cash
        self.where.setdefault(u, set()).add(g)

    def _pop(self, g: int, u: int) -> float:
        cash = self.rows[g].pop(u)
        w = self.where[u]
        w.discard(g)
        if not w:
            del self.where[u]
        return cash

    # -- one step -----------------------------------------------------------

    def step(self, urls: Sequence[int]) -> None:
        """Advance one crawl step given the program's fetched URLs of that
        step, in the program's row-major order (row, then lane)."""
        res = self.result
        t = self.t
        r_local = self.r_local
        fetched = [[] for _ in range(self.n_shards)]   # (row, url, cash)
        poppable = [0] * self.n_shards
        for g in range(self.n_slots):
            if self.rows[g]:
                poppable[g // r_local] += min(self.k_row, len(self.rows[g]))
        last = -1
        taken: Dict[int, int] = {}
        for u in (int(x) for x in urls):
            cands = sorted(g for g in self.where.get(u, ())
                           if g >= last and taken.get(g, 0) < self.k_row)
            if not cands:
                res.invalid += 1
                res.note(f"step {t}: fetched URL {u} is queued in no row "
                         f"that could fetch it")
                continue
            g = cands[0]
            last = g
            taken[g] = taken.get(g, 0) + 1
            fetched[g // r_local].append((g, u, self._pop(g, u)))
        for s in range(self.n_shards):
            want = min(self.fetch_batch, poppable[s])
            if len(fetched[s]) < want:
                res.short += want - len(fetched[s])
                res.note(f"step {t}: shard {s} fetched {len(fetched[s])} "
                         f"pages of a budget of {want}")
        res.fetched += sum(len(f) for f in fetched)
        if self.bounds is not None:
            self.since += 1
            self._judge_order(fetched)
        for s in range(self.n_shards):
            self._spend_and_stage(s, fetched[s])
        if (t + 1) % self.interval == 0:
            self._dispatch()
            self._rescore()
        self.t += 1
        res.steps += 1

    def _judge_order(self, fetched) -> None:
        """Count fetched URLs that a URL of a higher priority bucket should
        have preceded.

        Each step a row pops its top ``k_row`` URLs (highest bucket first),
        and the shard fetches the ``fetch_batch`` best pops; the rest go
        back to their rows with a bucket scored anew, which the reference
        does not follow. A row whose pops went back ``m`` times since the
        rescore may hold ``m`` URLs whose bucket moved, so a fetched URL
        must rank at least with the ``m + 1``-th best of the rest of its
        row, by the buckets of the rescore (by induction over the steps,
        also where it was itself given back before). At the first step
        after a rescore nothing has gone back yet, so a fetched URL must
        also rank at least with the best URL of each row of its shard that
        fetched nothing."""
        k, res = self.k_row, self.result
        for s in range(self.n_shards):
            per_row: Dict[int, list] = {}
            for g, u, _ in fetched[s]:
                lo, hi = self.bounds[g].pop(u)
                self.lo_count[g, lo] -= 1
                per_row.setdefault(g, []).append((u, hi))
            best_passed = -1
            if self.since == 1:
                rows = range(s * self.r_local, (s + 1) * self.r_local)
                best_passed = max((self._best(g, 0) for g in rows
                                   if g not in per_row and self.rows[g]),
                                  default=-1)
            for g, got in per_row.items():
                m = (self.since - 1) * k + (k - len(got))
                best = self._best(g, m)
                for u, hi in got:
                    if hi < max(best, best_passed):
                        res.order += 1
                        res.note(f"step {self.t}: row {g} fetched URL {u} "
                                 f"(bucket at most {hi}) over a URL of "
                                 f"bucket {max(best, best_passed)}")

    def _best(self, g: int, m: int) -> int:
        """The lowest bucket of the ``m + 1``-th best URL queued in row
        ``g`` (-1 when it holds no more than ``m``)."""
        n = 0
        for b in range(self.n_buckets - 1, -1, -1):
            n += self.lo_count[g, b]
            if n > m:
                return b
        return -1

    def _rescore(self) -> None:
        """Each queued URL's possible buckets after a dispatch's rescore."""
        self.bounds = [dict() for _ in range(self.n_slots)]
        self.lo_count[:] = 0
        self.since = 0
        for g in range(self.n_slots):
            row = self.rows[g]
            if not row:
                continue
            urls = np.fromiter(row.keys(), np.uint32, len(row))
            lo, hi = self._bucket_bounds(
                g, urls, np.fromiter(row.values(), np.float64, len(row)))
            self.bounds[g] = dict(zip(urls.tolist(),
                                      zip(lo.tolist(), hi.tolist())))
            np.add.at(self.lo_count[g], lo, 1)

    def _bucket_bounds(self, g: int, urls: np.ndarray, val=None):
        """The lowest and highest priority bucket opic_url's rescore can
        give the URLs queued in row ``g``: 0.4 x the slot's importance
        relative to the shard's most important slot, plus 0.15 x the URL's
        cash relative to its row's mean, plus 0.45 x its popularity (URLs
        of another shard's domain: 0.7 x popularity + 0.2 if a hub), in
        ``n_buckets`` buckets. The program computes the score in float32,
        so a score within 1e-4 of a bucket edge may land on either side."""
        web, r_local = self.web, self.r_local
        lo_row = g // r_local * r_local
        imp = self.importance[lo_row:lo_row + r_local]
        rel = imp / max(imp.max(), 1e-6)
        if val is None:
            row = self.rows[g]
            val = np.array([row.get(int(u), 0.0) for u in urls])
        mean = val.sum() / max((val > 0).sum(), 1)
        pop = web.popularity(urls).astype(np.float64)
        dom_row = self.slot_of_domain[web.domain_of(urls)] - lo_row
        local = (dom_row >= 0) & (dom_row < r_local)
        s_imp = rel[np.clip(dom_row, 0, r_local - 1)]
        s_url = val / (val + max(mean, 1e-9))
        score = np.where(local, 0.4 * s_imp + 0.15 * s_url + 0.45 * pop,
                         0.7 * pop + 0.2 * (pop > 0.95))
        nb = self.n_buckets
        score = np.clip(score, 0.0, 0.999) * nb
        near = np.round(score)
        edge = np.abs(score - near) < 1e-4
        lo = np.where(edge, near - 1, np.floor(score))
        hi = np.where(edge, near, np.floor(score))
        return (np.clip(lo, 0, nb - 1).astype(np.int64),
                np.clip(hi, 0, nb - 1).astype(np.int64))

    def _spend_and_stage(self, s: int, fetched) -> None:
        if not fetched:
            return
        web = self.web
        rows = np.array([g for g, _, _ in fetched])
        urls = np.array([u for _, u, _ in fetched], np.uint32)
        cells = np.array([c for _, _, c in fetched])
        n_f = {}
        for g in rows:
            n_f[g] = n_f.get(g, 0) + 1
        spend_slot = {g: self.slot_cash[g] for g in n_f}
        for g in n_f:
            self.slot_cash[g] = 0.0
        spend = np.array([spend_slot[g] / n_f[g] for g in rows]) + cells
        np.add.at(self.history, rows, spend)
        links = web.canonical(web.outlinks(urls).reshape(-1))
        src = np.repeat(web.domain_of(urls), self.O)
        srow = np.repeat(rows, self.O)
        val = np.repeat(spend / self.O, self.O)
        seen = set()
        stage = self.staging[s]
        for u, d, r, v in zip(links.tolist(), src.tolist(), srow.tolist(),
                              val.tolist()):
            if u in seen or len(stage) >= self.S:
                self.slot_cash[r] += v          # de-duplicated or overflow
                continue
            seen.add(u)
            stage.append((u, d, v))

    def _dispatch(self) -> None:
        web = self.web
        r_local = self.r_local
        # the whole-queue rescore at the end of this dispatch ranks by the
        # slot importance as it stood when the dispatch began
        self.importance = self.slot_cash + self.history
        inbox = [[[] for _ in range(self.n_shards)]
                 for _ in range(self.n_shards)]          # [dest][source]
        for s in range(self.n_shards):
            stage = self.staging[s]
            if not stage:
                continue
            u = np.array([x[0] for x in stage], np.uint32)
            src = np.array([x[1] for x in stage], np.int32)
            pred = web.predict_domain(u, src, self.t, self.accuracy)
            dest = self.slot_of_domain[pred] // r_local
            count = [0] * self.n_shards
            for (uu, d, v), p, ds in zip(stage, pred.tolist(), dest.tolist()):
                if count[ds] < self.cap_ex:
                    count[ds] += 1
                    inbox[ds][s].append((uu, p, v))
                else:                           # bucket overflow: refund
                    own = int(self.slot_of_domain[d]) - s * r_local
                    own = min(max(own, 0), r_local - 1)
                    self.slot_cash[s * r_local + own] += v
            self.staging[s] = []
        for s in range(self.n_shards):
            seen = set()
            per_row: Dict[int, list] = {}
            for src_shard in range(self.n_shards):
                for u, p, v in inbox[s][src_shard]:
                    g = int(self.slot_of_domain[p])
                    if u in seen:               # exact duplicate: refund
                        self.slot_cash[g] += v
                        continue
                    seen.add(u)
                    per_row.setdefault(g, []).append((u, v))
            for g, items in per_row.items():
                row, bloom = self.rows[g], self.bloom[g]
                free = self.capacity - len(row)
                placed = 0
                for u, v in items[self.row_bucket:]:
                    self.slot_cash[g] += v      # row bucket overflow: refund
                items = items[:self.row_bucket]
                bits = self._bits([u for u, _ in items])
                # the filter streams tiles of URLs: a tile probes it after
                # the earlier tiles inserted, and before its own inserts
                for lo in range(0, len(items), BLOOM_TILE):
                    tile = range(lo, min(lo + BLOOM_TILE, len(items)))
                    hit = [all(b in bloom for b in bits[i]) for i in tile]
                    for i, was in zip(tile, hit):
                        u, v = items[i]
                        if was:
                            if u in row:        # queued twin: deposit
                                row[u] += v
                            else:
                                self.slot_cash[g] += v
                        elif placed < free:
                            self._queue(g, u, v)
                            placed += 1
                        else:                   # queue full: refund
                            self.slot_cash[g] += v
                    for i in tile:
                        bloom.update(bits[i])

    # -- comparisons ----------------------------------------------------------

    def compare_final(self, f_url: np.ndarray, f_valid: np.ndarray,
                      f_pri: np.ndarray, order_state: np.ndarray
                      ) -> Dict[str, float]:
        """The program's state after its last step against the reference:
        queued URL sets per row, each queued URL's cash, slot cash and
        history (worst gap relative to the larger of the reference value
        and the median non-zero reference value), and total cash."""
        mismatch = 0
        gaps = []
        ref_cash, got_cash = [], []
        for g in range(self.n_slots):
            got = dict(zip(f_url[g][f_valid[g]].tolist(),
                           order_state[g, 2:][f_valid[g]].tolist()))
            ref = self.rows[g]
            mismatch += len(set(got) ^ set(ref))
            for u in set(got) & set(ref):
                ref_cash.append(ref[u])
                got_cash.append(got[u])
        ref_cash = np.array(ref_cash)
        got_cash = np.array(got_cash)
        for ref, got in ((ref_cash, got_cash),
                         (self.slot_cash, order_state[:, 0]),
                         (self.history, order_state[:, 1])):
            nz = np.abs(ref[ref != 0])
            scale = np.maximum(np.abs(ref), np.median(nz) if len(nz) else 1.0)
            if len(ref):
                gaps.append(float(np.max(np.abs(got - ref) / scale)))
        total = float(order_state[:, 0].astype(np.float64).sum()
                      + order_state[:, 2:].astype(np.float64).sum())
        return dict(queue_mismatch=float(mismatch),
                    cash_gap=max(gaps) if gaps else 0.0,
                    cash_drift=abs(total - self.cash0) / self.cash0,
                    rescore_mismatch=float(self.rescore_mismatch(
                        f_url, f_valid, f_pri)))

    def rescore_mismatch(self, f_url, f_valid, f_pri) -> int:
        """Queued URLs whose priority bucket after the last dispatch is not
        one that opic_url's score can give them (``_bucket_bounds``)."""
        bad = 0
        for g in range(self.n_slots):
            urls = f_url[g][f_valid[g]]
            if not len(urls):
                continue
            lo, hi = self._bucket_bounds(g, urls)
            got = np.ceil(f_pri[g][f_valid[g]].astype(np.float64)
                          / (1 << 20))
            bad += int(((got < lo) | (got > hi)).sum())
        return bad

    def bloom_mismatch(self, bits) -> int:
        """Bit positions set in one side's Bloom rows and not the other's.

        ``bits`` is the program's filter, (rows, 2^bits_log2) with one byte
        a bit, read where it lies: each row's count of set bits, and the
        program's bit at every position the reference set."""
        import jax.numpy as jnp
        rows = np.concatenate([np.full(len(b), g, np.int32)
                               for g, b in enumerate(self.bloom)])
        pos = np.concatenate([np.fromiter(b, np.int32, len(b))
                              for b in self.bloom])
        n_got = np.asarray(jnp.count_nonzero(bits, axis=1), np.int64)
        have = np.asarray(bits[jnp.asarray(rows), jnp.asarray(pos)]) != 0
        missing = int((~have).sum())
        n_ref = np.array([len(b) for b in self.bloom], np.int64)
        extra = int(np.abs(n_got - (n_ref - np.bincount(
            rows[~have], minlength=self.n_slots))).sum())
        return missing + extra
