"""The serve layer (repro/serve, DESIGN.md §16): incremental indexing
bit-identity, seeded query determinism, serve-state checkpoint/restore,
serving across a fail/heal cycle, and the index-capacity mask regression.

Like tests/test_invariants.py, the crawl-side knobs honor the CI matrix:
``REPRO_KERNEL_IMPL`` / ``REPRO_COORDINATION`` / ``REPRO_FUSED_DISPATCH``
replay the whole suite per kernel implementation and coordination mode —
the serve layer must hold under every crawl configuration that feeds it."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.dispatch import BACKEND_COMPILE_EVENT

from repro.configs import get_reduced
from repro.configs.base import scaled
from repro.core import index as IX
from repro.serve import QueryBatch, QueryLoad, ServeSession

CFG = scaled(get_reduced("webparf"),
             kernel_impl=os.environ.get("REPRO_KERNEL_IMPL", "auto"),
             coordination=os.environ.get("REPRO_COORDINATION", "exchange"),
             fused_dispatch=os.environ.get("REPRO_FUSED_DISPATCH", "1")
             != "0")
IV = CFG.dispatch_interval
VOCAB, DOC_LEN, K = 512, 16, 5


def make_sess(cfg=CFG, *, qps=3.0, seed=0, index_capacity=1024, **kw):
    load = QueryLoad(cfg, qps=qps, seed=seed)
    kw.setdefault("doc_len", DOC_LEN)
    kw.setdefault("vocab", VOCAB)
    kw.setdefault("top_k", K)
    return ServeSession(cfg, load=load, index_capacity=index_capacity, **kw)


# ---------------------------------------------------------------------------
# the load generator
# ---------------------------------------------------------------------------

def test_load_deterministic_and_seekable():
    a = QueryLoad(CFG, qps=4.0, seed=11)
    b = QueryLoad(CFG, qps=4.0, seed=11)
    qa = a.take(0, 12.0)
    # consume b in three uneven slices: same schedule, any chunking
    q1 = b.take(0, 3.5)
    q2 = b.take(q1.cursor, 9.0)
    q3 = b.take(q2.cursor, 12.0)
    np.testing.assert_array_equal(
        qa.time, np.concatenate([q1.time, q2.time, q3.time]))
    np.testing.assert_array_equal(
        qa.seed, np.concatenate([q1.seed, q2.seed, q3.seed]))
    np.testing.assert_array_equal(
        qa.domain, np.concatenate([q1.domain, q2.domain, q3.domain]))
    assert (np.diff(qa.time) >= 0).all()
    c = QueryLoad(CFG, qps=4.0, seed=12).take(0, 12.0)
    assert len(c) != len(qa) or not np.array_equal(c.seed, qa.seed)


def test_load_zipf_skew_and_burst():
    load = QueryLoad(CFG, qps=8.0, seed=3, burst_prob=1.0, burst_mult=4.0)
    flat = QueryLoad(CFG, qps=8.0, seed=3, burst_prob=0.0)
    assert load.arrivals_until(32.0) > 2 * flat.arrivals_until(32.0)
    q = flat.take(0, 64.0)
    counts = np.bincount(q.domain, minlength=CFG.n_domains)
    assert counts[0] > counts[CFG.n_domains - 1]       # head-heavy mix
    assert (q.domain < CFG.n_domains).all()


# ---------------------------------------------------------------------------
# incremental indexing == one batch build, bit for bit
# ---------------------------------------------------------------------------

def test_incremental_index_equals_batch_built():
    """The session's per-interval folds must replay as ONE add_batch of the
    full page stream (the incremental-indexing contract)."""
    sess = make_sess(qps=0.0)
    rep = sess.run(3 * IV, recall=False)
    assert sess.n_shards == 1          # host test; sharded cell is below
    urls = rep.crawl.urls
    assert len(urls) > 0
    expected = IX.add_batch(
        IX.init_index(sess.cap_shard, DOC_LEN, VOCAB),
        jnp.asarray(urls.astype(np.uint32)),
        jnp.ones((len(urls),), bool), CFG)
    for name, got, want in zip(IX.Index._fields, sess.index, expected):
        np.testing.assert_array_equal(
            np.asarray(got)[0], np.asarray(want),
            err_msg=f"Index.{name}: incremental != batch-built")
    assert sess.watermark == 3 * IV


def test_sharded_search_matches_single_index_scores():
    """Global df/N psum: the sharded query path must agree with an
    unsharded index over the same docs (1 shard -> trivially the same
    partition; the scoring path is identical code either way)."""
    sess = make_sess(qps=0.0)
    sess.run(2 * IV, recall=False)
    urls = np.asarray(sess.index.doc_url[0])
    urls = urls[urls != 0]
    single = IX.add_batch(IX.init_index(1024, DOC_LEN, VOCAB),
                          jnp.asarray(urls.astype(np.uint32)),
                          jnp.ones((len(urls),), bool), CFG)
    q = IX.query_terms(9, 8, VOCAB, domain=2, cfg=CFG)
    s_ref, u_ref = IX.search(single, q, k=K)
    s_live, u_live = sess.answer([2], seeds=[9])
    np.testing.assert_allclose(np.asarray(s_live[0]), np.asarray(s_ref),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(u_live[0]), np.asarray(u_ref))


# ---------------------------------------------------------------------------
# seeded determinism of the query path
# ---------------------------------------------------------------------------

def test_query_path_deterministic_under_fixed_seed():
    ra = make_sess(qps=4.0, seed=5).run(2 * IV, recall=False)
    rb = make_sess(qps=4.0, seed=5).run(2 * IV, recall=False)
    assert ra.n_queries == rb.n_queries > 0
    np.testing.assert_array_equal(ra.arrival_step, rb.arrival_step)
    np.testing.assert_array_equal(ra.top_urls, rb.top_urls)
    np.testing.assert_array_equal(ra.top_scores, rb.top_scores)
    np.testing.assert_array_equal(ra.lag_steps, rb.lag_steps)
    assert (ra.lag_steps <= IV).all() and (ra.lag_steps >= 1).all()


def test_report_shapes_and_percentiles():
    rep = make_sess(qps=4.0, seed=1).run(2 * IV, recall=True)
    n = rep.n_queries
    assert rep.latency_ms.shape == (n,)
    assert rep.top_urls.shape == (n, K)
    assert rep.p50_ms <= rep.p95_ms <= rep.p99_ms
    assert rep.qps > 0 and rep.seconds > 0
    assert 0.0 <= rep.recall_at_k <= 1.0
    m = rep.metrics()
    for key in ("qps", "p50_ms", "p99_ms", "freshness_lag_steps",
                "index_docs", "index_dropped", f"recall_at_{K}"):
        assert key in m, m


class _SizedLoad:
    """A load whose takes hand over a set number of queries each."""

    def __init__(self, sizes, n_domains):
        self.sizes, self.n_domains = list(sizes), n_domains

    def take(self, cursor, t_now):
        n = self.sizes.pop(0) if self.sizes else 0
        rng = np.random.default_rng(cursor)
        return QueryBatch(
            time=np.full(n, t_now - 0.5),
            domain=rng.integers(0, self.n_domains, n).astype(np.int32),
            seed=rng.integers(1, 1 << 31, n).astype(np.uint32),
            cursor=cursor + n)


def test_partial_batches_compile_nothing():
    """After two intervals of full batches (the first query and fold see
    the initial index's placement, the second the folded one's), intervals
    that end in a partial batch of any size compile nothing: the answers
    are cut to size on the host, so no compile lands in a serving window."""
    B = 4
    sizes = [B, B, 1, B + 2, 2 * B + 3]
    sess = make_sess(query_batch=B)
    sess.load = _SizedLoad(sizes, CFG.n_domains)
    sess.run(2 * IV, recall=False)             # compiles, full batches only
    compiles = []

    def listen(event, secs, **kw):
        if event == BACKEND_COMPILE_EVENT:
            compiles.append(secs)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        rep = sess.run(3 * IV, recall=False)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
    assert rep.top_urls.shape == (sum(sizes[2:]), K)
    assert rep.top_scores.shape == (sum(sizes[2:]), K)


# ---------------------------------------------------------------------------
# checkpoint / restore: serving resumes where it left off
# ---------------------------------------------------------------------------

def test_checkpoint_restore_roundtrips_index_mid_crawl(tmp_path):
    d = str(tmp_path / "ck")
    a = make_sess(qps=3.0, seed=2)
    a.run(2 * IV, recall=False)
    a.checkpoint(d)
    cursor, watermark = a._q_cursor, a.watermark
    ra = a.run(2 * IV, recall=False)

    b = make_sess(qps=3.0, seed=2)            # fresh session, same schedule
    b.restore(d)
    assert b.t == 2 * IV
    assert b.watermark == watermark and b._q_cursor == cursor
    rb = b.run(2 * IV, recall=False)

    # identical continuation: same queries fired, same answers, same index
    assert ra.n_queries == rb.n_queries
    np.testing.assert_array_equal(ra.arrival_step, rb.arrival_step)
    np.testing.assert_array_equal(ra.top_urls, rb.top_urls)
    np.testing.assert_array_equal(ra.top_scores, rb.top_scores)
    for name, x, y in zip(IX.Index._fields, a.index, b.index):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"Index.{name} after restore")


def test_checkpoint_folds_pending_intervals(tmp_path):
    d = str(tmp_path / "ck")
    sess = make_sess(qps=0.0, index_every=4)
    sess.run(2 * IV, recall=False)
    assert sess.watermark == 0                # folds deferred
    assert int(np.asarray(sess.index.n_docs).sum()) == 0
    sess.checkpoint(d)                        # must flush before saving
    assert sess.watermark == 2 * IV
    assert int(np.asarray(sess.index.n_docs).sum()) > 0


# ---------------------------------------------------------------------------
# index capacity: mask, never wrap/overwrite — and the stat surfaces
# ---------------------------------------------------------------------------

def test_add_batch_masks_at_capacity_and_counts_drops():
    idx = IX.init_index(8, DOC_LEN, VOCAB)
    idx = IX.add_batch(idx, jnp.arange(1, 7, dtype=jnp.uint32),
                       jnp.ones(6, bool), CFG)
    assert int(idx.n_dropped) == 0
    before = np.asarray(idx.doc_url).copy()
    idx = IX.add_batch(idx, jnp.arange(10, 16, dtype=jnp.uint32),
                       jnp.ones(6, bool), CFG)
    assert int(idx.n_docs) == 8                    # capacity-bounded
    assert int(idx.n_dropped) == 4                 # refused, counted
    np.testing.assert_array_equal(np.asarray(idx.doc_url)[:6], before[:6])
    idx2 = IX.add_batch(idx, jnp.arange(20, 24, dtype=jnp.uint32),
                        jnp.ones(4, bool), CFG)
    # full index: nothing overwritten, everything refused is counted,
    # masked-out lanes are NOT counted
    np.testing.assert_array_equal(np.asarray(idx2.doc_url),
                                  np.asarray(idx.doc_url))
    np.testing.assert_array_equal(np.asarray(idx2.df), np.asarray(idx.df))
    assert int(idx2.n_dropped) == 8
    idx3 = IX.add_batch(idx2, jnp.arange(30, 34, dtype=jnp.uint32),
                        jnp.zeros(4, bool), CFG)
    assert int(idx3.n_dropped) == 8


def test_session_surfaces_index_full():
    cfg = scaled(CFG, seed_urls_per_domain=8)
    sess = make_sess(cfg, qps=2.0, seed=4, index_capacity=32, top_k=5)
    filled = None
    for _ in range(4):
        rep = sess.run(IV, recall=False)
        if filled is None and sess.index_stats()["index_docs"] == 32:
            filled = np.asarray(sess.index.doc_url).copy()
    st = sess.index_stats()
    assert st["index_docs"] == 32                 # never exceeds capacity
    assert st["index_dropped"] > 0                # drops surfaced
    assert rep.index_full and rep.metrics()["index_dropped"] > 0
    assert filled is not None
    np.testing.assert_array_equal(np.asarray(sess.index.doc_url), filled,
                                  err_msg="full index was overwritten")


# ---------------------------------------------------------------------------
# serving across a fail/heal cycle (4 forced shards, subprocess)
# ---------------------------------------------------------------------------

FAIL_HEAL = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, "src")
    import numpy as np
    from repro.configs import get_reduced
    from repro.serve import QueryLoad, ServeSession

    cfg = get_reduced("webparf")
    iv = cfg.dispatch_interval
    sess = ServeSession(cfg, load=QueryLoad(cfg, qps=4.0, seed=0),
                        index_capacity=1024, doc_len=16, vocab=512, top_k=5)
    assert sess.n_shards == 4
    r0 = sess.run(iv, recall=False)
    docs0 = sess.index_stats()["index_docs"]
    assert docs0 > 0

    sess.inject_failure(1)                 # shard dies mid-crawl
    r1 = sess.run(iv, recall=False)        # stale but correct: still serving
    assert r1.n_queries > 0
    assert np.isfinite(r1.top_scores).any()
    docs1 = sess.index_stats()["index_docs"]
    assert docs1 >= docs0                  # index never regresses

    sess.heal()                            # rebalance onto survivors
    r2 = sess.run(iv, recall=False)
    assert r2.n_queries > 0
    docs2 = sess.index_stats()["index_docs"]
    assert docs2 > docs1                   # crawl feeds the index again
    # determinism holds through the cycle: replay the same schedule
    replay = ServeSession(cfg, load=QueryLoad(cfg, qps=4.0, seed=0),
                          index_capacity=1024, doc_len=16, vocab=512,
                          top_k=5)
    replay.run(iv, recall=False)
    replay.inject_failure(1)
    q1 = replay.run(iv, recall=False)
    np.testing.assert_array_equal(q1.top_urls, r1.top_urls)
    print("serve fail/heal cycle: OK")
""")


@pytest.mark.slow
def test_serving_continues_across_fail_heal_multi_shard():
    r = subprocess.run([sys.executable, "-c", FAIL_HEAL],
                       capture_output=True, text=True, timeout=900, cwd=".")
    if r.returncode != 0:
        raise AssertionError(f"STDOUT:\n{r.stdout[-3000:]}\n"
                             f"STDERR:\n{r.stderr[-3000:]}")
    assert "serve fail/heal cycle: OK" in r.stdout
