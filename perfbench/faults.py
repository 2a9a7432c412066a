"""Changes planted in the program for one run: the control and the faults.

Each is a function of the run's driver that returns a context manager;
``harness.run_cell(..., tamper=...)`` holds it open for the whole run. The
benchmark's own runs plant nothing. ``control.py`` runs the controls on the
chip at the cells' own sizes, and ``tests/test_faults.py`` runs everything
at a size the CPU holds; both expect ``correct`` to come out false.

Controls (a guarantee the configuration states, broken on purpose):

* ``hash_rows``: each arriving URL is queued in a row picked by a hash of
  the URL (the ``url_hash`` baseline's placement, which skips the domain
  prediction) instead of its predicted domain's row (breaks "every URL is
  queued in the row of its predicted domain");
* ``bloom_forget``: the crawl's Bloom rows are cleared before every chunk,
  as a filter cut to save memory would forget, so URLs already crawled can
  be queued again (breaks "a URL is queued at most once"; a window
  re-discovers few URLs, but the filter's bits no longer match the
  reference's);
* ``bf16_scores``: the search path scores in bfloat16, the precision below
  the float32 it states.

Faults (the timed path broken underneath):

* ``state_unchanged``: the chunk returns the state it was given;
* ``half_batch``: the pages of every other row are left out of the reports;
* ``no_exchange``: the all_to_all is left out, each shard keeps its buckets;
* ``altered_fetch``: one fetched URL is altered in the report;
* ``altered_answer``: one served URL is altered in every answer batch;
* ``skip_rescore``: the dispatch's whole-queue rescore is left out;
* ``pop_lowest``: the frontier select pops each row's lowest-priority URL
  instead of its highest.
"""
from __future__ import annotations

from unittest import mock


def _wrap_chunk(wrap):
    """Patch the program's chunk builder so each built chunk is wrapped."""
    import repro.api.session as S
    build = S.chunk_program

    def patched(*a, **kw):
        return wrap(build(*a, **kw))
    return mock.patch.object(S, "chunk_program", patched)


def hash_rows(drv):
    import repro.core.partitioner as PT
    webparf = PT.get_policy("webparf")
    return mock.patch.dict(PT._POLICIES, {
        "webparf": webparf._replace(local_row=PT._hash_row)})


def bloom_forget(drv):
    import jax
    import jax.numpy as jnp
    forget = jax.jit(lambda st: st._replace(
        bloom_bits=jnp.zeros_like(st.bloom_bits)), donate_argnums=0)

    def wrap(chunk):
        return lambda st: chunk(forget(st))
    return _wrap_chunk(wrap)


def state_unchanged(drv):
    def wrap(chunk):
        def run(st):
            _, reps = chunk(st)
            return st, reps
        return run
    return _wrap_chunk(wrap)


def half_batch(drv):
    import jax.numpy as jnp

    def wrap(chunk):
        def run(st):
            st2, reps = chunk(st)
            rows = reps.fetched_mask.shape[1]
            keep = (jnp.arange(rows) % 2 == 0)[None, :, None]
            return st2, reps._replace(
                fetched_mask=reps.fetched_mask & keep,
                fetched_urls=jnp.where(keep, reps.fetched_urls, 0))
        return run
    return _wrap_chunk(wrap)


def altered_fetch(drv):
    import jax.numpy as jnp

    def wrap(chunk):
        def run(st):
            st2, reps = chunk(st)
            m = reps.fetched_mask
            first = jnp.cumsum(m.reshape(-1)).reshape(m.shape) == 1
            return st2, reps._replace(fetched_urls=jnp.where(
                first & m, reps.fetched_urls ^ 1, reps.fetched_urls))
        return run
    return _wrap_chunk(wrap)


def no_exchange(drv):
    import repro.core.router as RT
    return mock.patch.object(RT, "exchange", lambda buckets, axes: buckets)


def altered_answer(drv):
    import repro.serve.query as Q
    build = Q.make_query_fn

    def patched(*a, **kw):
        fn = build(*a, **kw)

        def run(index, seeds, doms):
            s, u = fn(index, seeds, doms)
            return s, u.at[0, 0].set(u[0, 0] ^ 1)
        return run
    return mock.patch.object(Q, "make_query_fn", patched)


def skip_rescore(drv):
    import repro.core.frontier as F
    return mock.patch.object(F, "rescore", lambda f, scores, **kw: f)


def pop_lowest(drv):
    import jax.numpy as jnp
    import repro.core.frontier as F
    select = F.select_harvest

    def flipped(f, table, k, *, impl="ref"):
        neg = f._replace(priority=jnp.where(f.valid, -f.priority, F.NEG))
        urls, pri, mask, f2, idx, cash, table2 = select(neg, table, k,
                                                        impl=impl)
        f2 = f2._replace(priority=jnp.where(f2.valid, -f2.priority, F.NEG))
        return urls, jnp.where(mask, -pri, pri), mask, f2, idx, cash, table2
    return mock.patch.object(F, "select_harvest", flipped)


def bf16_scores(drv):
    """The search path's scoring in bfloat16: the program's own query
    program, written again with its tf-idf arithmetic one precision down."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    import repro.serve.query as Q
    from perfbench.searchref import hash2

    def make(cfg, mesh, axes, *, n_terms, k):
        @jax.jit
        def run(index, seeds, doms):
            toks = index.doc_tokens[0]
            vocab = index.df.shape[-1]
            b = vocab // max(int(cfg.n_domains), 1)
            h = hash2(seeds[:, None].astype(jnp.uint32),
                       jnp.arange(n_terms, dtype=jnp.uint32)[None, :], 91)
            terms = doms[:, None] * b + (h % jnp.uint32(max(b, 1))
                                         ).astype(jnp.int32)
            n = jnp.maximum(index.n_docs[0], 1).astype(jnp.bfloat16)
            df = index.df[0][terms].astype(jnp.bfloat16)
            idf = jnp.log1p(n / (1.0 + df))                   # (B, Q)

            def one(t, w):
                tf = (toks[:, :, None] == t[None, None, :]).sum(1)
                s = (jnp.log1p(tf.astype(jnp.bfloat16)) * w[None, :]).sum(1)
                return jnp.where(index.doc_valid[0],
                                 s.astype(jnp.float32), -jnp.inf)
            scores = lax.map(lambda a: one(*a), (terms, idf))
            s, i = lax.top_k(scores, k)
            return s, index.doc_url[0][i]
        return run
    return mock.patch.object(Q, "make_query_fn", make)


CONTROLS = {"hash_rows": hash_rows, "bloom_forget": bloom_forget,
            "bf16_scores": bf16_scores}
FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "no_exchange": no_exchange, "altered_fetch": altered_fetch,
          "altered_answer": altered_answer, "skip_rescore": skip_rescore,
          "pop_lowest": pop_lowest}
