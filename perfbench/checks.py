"""The numbers that decide ``correct``, each against its limit.

Crawl (every cell), from ``crawlref``'s replay of every step since the
crawl started:

  invalid_fetch   fetched URLs that no row of the fetching shard held
  pop_order       fetched URLs that a queued URL of a higher priority
                  bucket should have preceded (in its row, or at the first
                  step after a rescore in its shard's fetch budget)
  budget_short    pages the shards' fetch budgets left unfetched
  queue_mismatch  URLs queued at the end by one side and not the other
  bloom_mismatch  Bloom bit positions set at the end by one side and not
                  the other
  cash_gap        worst gap of a queued URL's cash, a slot's cash or its
                  history, relative to the reference's
  cash_drift      total OPIC cash against its start (the configuration
                  states conservation to a relative 1e-4)
  rescore_mismatch queued URLs whose priority bucket is not the one the
                  ordering's score gives them after the last dispatch

Search (cells with search traffic), from ``searchref`` over a sample of
the answered queries drawn from the seed:

  topk_gap        worst gap of a served top-k score, rank by rank, or of a
                  served page's score, against the reference
  unanswered      queries due in the window never answered
"""
from __future__ import annotations

import sys
from typing import Dict, List

import numpy as np

from perfbench.crawlref import CrawlReference
from perfbench.webref import Web


def check_crawl(cell, n_shards: int, steps: List[np.ndarray],
                final: Dict[str, np.ndarray], bloom) -> Dict[str, float]:
    ref = CrawlReference(cell.config["crawl"], n_shards,
                         cell.config["classify_accuracy"])
    for urls in steps:
        ref.step(urls)
    got = ref.compare_final(final["f_url"], final["f_valid"],
                            final["f_pri"], final["order_state"])
    for line in ref.result.examples:
        print("  reference:", line, file=sys.stderr)
    return dict(invalid_fetch=float(ref.result.invalid),
                pop_order=float(ref.result.order),
                budget_short=float(ref.result.short),
                bloom_mismatch=float(ref.bloom_mismatch(bloom)), **got)


def check_search(cell, seed: int, steps: List[np.ndarray], *, answers,
                 prefill, load) -> Dict[str, float]:
    import jax.numpy as jnp
    from perfbench import searchref as S
    s = cell.traffic["search"]
    web = Web(cell.config["crawl"])
    k, n_terms, vocab = s["top_k"], s["terms"], s["vocab"]
    cap = int(s["index_prefill"]) + int(s["index_room"])
    kw = dict(local_bits=web.local_bits, alias_start=int(web.alias_start),
              n_domains=web.n_domains, n_tokens=s["doc_len"], vocab=vocab)
    crawled = (np.concatenate(steps) if steps else np.empty(0, np.uint32))
    tokens = index_tokens(prefill, crawled.astype(np.uint32), cap, kw)
    # every answered query, as (answer record, position in it)
    pool = [(a, j) for a in answers for j in range(a[2] - a[1])]
    rng = np.random.default_rng([int(seed) % (1 << 64), 0x636B])
    pick = rng.choice(len(pool), size=min(len(pool), int(s["check_queries"])),
                      replace=False) if pool else []
    worst = 0.0
    for i in sorted(pick):
        (wall, lo, hi, lat, urls, scores, visible, prev), j = pool[i]
        qseed, qdom = load.queries(lo + j, lo + j + 1)
        terms = web.query_terms(qseed, qdom, n_terms, vocab)[0]
        top, idf = S.topk_scores(tokens, visible, jnp.asarray(terms), k=k)
        own = S.page_scores(S.page_tokens(jnp.asarray(urls[j]), **kw),
                            jnp.asarray(terms), idf)
        worst = max(worst, S.gap(scores[j], np.asarray(top)),
                    S.gap(scores[j], np.asarray(own)))
    return dict(topk_gap=worst)


def index_tokens(prefill, crawled: np.ndarray, cap: int, kw):
    """The reference's doc-token matrix in the index's own order (the
    pre-filled pages, then every crawled page as it was folded in), padded
    to the index capacity so that its shape is the same in every run."""
    import jax.numpy as jnp
    from perfbench import searchref as S
    parts = [S.page_tokens(p.fetched_urls.reshape(-1), **kw) for p in prefill]
    room = cap - sum(int(p.fetched_urls.size) for p in prefill)
    tail = np.zeros(room, np.uint32)
    n = min(room, len(crawled))
    tail[:n] = crawled[:n]
    parts.append(S.page_tokens(jnp.asarray(tail), **kw))
    return jnp.concatenate(parts)
