"""The references' copies of the web agree with the program's, so a sound
run is compared with the same semantics it runs."""
import dataclasses

import numpy as np
import pytest

from perfbench.webref import Web


@pytest.fixture(scope="module")
def cfg():
    from repro.configs.base import CrawlConfig
    return CrawlConfig()


@pytest.fixture(scope="module")
def urls():
    return np.random.default_rng(5).integers(1, 1 << 30, 4096).astype(
        np.uint32)


def test_web_matches_program(cfg, urls):
    import jax
    import jax.numpy as jnp
    from repro.core import classifier as CLS
    from repro.core import webgraph as W
    from repro.core.index import query_terms
    web = Web(dataclasses.asdict(cfg))
    u = jnp.asarray(urls)
    assert np.array_equal(web.canonical(urls), W.canonical(u, cfg))
    assert np.array_equal(web.outlinks(urls),
                          W.outlinks(u, cfg, W.zipf_cumweights(cfg)))
    assert np.array_equal(web.page_tokens(urls, 64, 4096),
                          W.page_tokens(u, cfg, n_tokens=64, vocab=4096))
    assert np.array_equal(web.hub_seeds(), W.hub_seeds(cfg))
    src = (urls % 256).astype(np.int32)
    assert np.array_equal(
        web.predict_domain(urls, src, 7, 0.9),
        CLS.predict_domain(u, jnp.asarray(src), cfg, step=7, accuracy=0.9))
    qt = jax.vmap(lambda s, d: query_terms(s, 8, 4096, d, cfg))(
        u[:64], jnp.asarray(src[:64]))
    assert np.array_equal(web.query_terms(urls[:64], src[:64], 8, 4096), qt)


def test_device_tokens_match_web(cfg, urls):
    import jax.numpy as jnp
    from perfbench import searchref as S
    web = Web(dataclasses.asdict(cfg))
    t = S.page_tokens(jnp.asarray(urls), local_bits=web.local_bits,
                      alias_start=int(web.alias_start),
                      n_domains=web.n_domains, n_tokens=64, vocab=4096)
    assert np.array_equal(np.asarray(t), web.page_tokens(urls, 64, 4096))


def test_search_reference_scores_by_hand():
    import jax.numpy as jnp
    from perfbench import searchref as S
    toks = jnp.asarray([[1, 1, 2, 3], [2, 2, 2, 9], [5, 6, 7, 8],
                        [1, 2, 9, 9]], jnp.int32)
    terms = jnp.asarray([1, 2], jnp.int32)
    top, idf = S.topk_scores(toks, 3, terms, k=2)   # doc 3 not yet indexed
    # df(1) = 1, df(2) = 2 over the 3 visible docs
    idf1, idf2 = np.log1p(3 / 2), np.log1p(3 / 3)
    want = sorted([np.log1p(2) * idf1 + np.log1p(1) * idf2,
                   np.log1p(3) * idf2], reverse=True)
    assert np.allclose(np.asarray(top), want, rtol=1e-6)
    own = S.page_scores(toks[3:], terms, idf)
    assert np.allclose(np.asarray(own), [np.log1p(1) * (idf1 + idf2)])
    assert S.gap([1.0, 2.0], [1.0, 2.0]) == 0.0
    assert S.gap([1.0, 1.9], [1.0, 2.0]) == pytest.approx(0.05)
