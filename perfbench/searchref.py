"""The plain reference of the search path: TF-IDF over every indexed doc.

For each checked query it scores every document the index held when the
query was answered (the pre-filled docs and the crawled pages folded before
it), with corpus-wide document frequencies, in float32 on the device:

    tf(d, t) = occurrences of term t in doc d
    idf(t)   = log(1 + N / (1 + df(t)))
    score(d) = sum over the query's terms of log(1 + tf(d, t)) * idf(t)

and compares the served top-k scores, rank by rank, with the reference's
top-k, and each served URL's score with the reference score of its page.
Documents are generated here from their URLs by the reference web's hash
arithmetic, written again in jax.numpy so that millions of them fit in a
second of device time (tests check it against ``webref``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

U32 = jnp.uint32


def _mix(x, salt: int):
    x = x.astype(U32) ^ U32((salt * 0x9E3779B9 + 0x85EBCA6B) & 0xFFFFFFFF)
    x = (x ^ (x >> 16)) * U32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * U32(0xC2B2AE35)
    return x ^ (x >> 16)


def hash2(a, b, salt: int):
    return _mix(a.astype(U32) + _mix(jnp.asarray(b, U32), salt + 7), salt)


@partial(jax.jit, static_argnames=("local_bits", "alias_start", "n_domains",
                                   "n_tokens", "vocab"))
def page_tokens(url, *, local_bits: int, alias_start: int, n_domains: int,
                n_tokens: int, vocab: int):
    """(n,) uint32 URLs -> (n, n_tokens) int32 terms (webref.page_tokens)."""
    url = url.astype(U32)
    local = url & U32((1 << local_bits) - 1)
    dom = (url >> local_bits).astype(jnp.int32)
    canon_local = _mix(local, 11) % U32(max(alias_start, 1))
    c = jnp.where(local >= U32(alias_start),
                  (dom.astype(U32) << local_bits) | canon_local, url)
    c = c[:, None]
    i = jnp.arange(n_tokens, dtype=U32)[None, :]
    h = hash2(c, i, 4)
    band = vocab // max(n_domains, 1)
    in_band = (hash2(c, i, 5).astype(jnp.float32)
               * jnp.float32(1.0 / 4294967296.0)) < jnp.float32(0.7)
    tok_band = dom[:, None] * band + (h % U32(max(band, 1))).astype(jnp.int32)
    tok_glob = (h % U32(vocab)).astype(jnp.int32)
    return jnp.where(in_band, tok_band, tok_glob)


def _tf(tokens, terms):
    """(D, L) docs, (Q,) terms -> (D, Q) term counts."""
    return (tokens[:, :, None] == terms[None, None, :]).sum(1)


@partial(jax.jit, static_argnames=("k", "dtype"))
def topk_scores(tokens, n_visible, terms, *, k: int, dtype=jnp.float32):
    """The k best scores over the first ``n_visible`` docs, and the idf of
    each term (to score any other page against the same corpus)."""
    visible = jnp.arange(tokens.shape[0]) < n_visible
    tf = _tf(tokens, terms)                                       # (D, Q)
    df = ((tf > 0) & visible[:, None]).sum(0)
    n = jnp.maximum(n_visible, 1).astype(jnp.float32)
    idf = jnp.log1p(n / (1.0 + df.astype(jnp.float32))).astype(dtype)
    s = (jnp.log1p(tf.astype(dtype)) * idf[None, :]).sum(1)
    s = jnp.where(visible, s.astype(jnp.float32), -jnp.inf)
    return jax.lax.top_k(s, k)[0], idf


@partial(jax.jit, static_argnames=("dtype",))
def page_scores(tokens, terms, idf, *, dtype=jnp.float32):
    """Scores of a few pages (P, L) for one query against a known idf."""
    tf = _tf(tokens, terms)
    return (jnp.log1p(tf.astype(dtype)) * idf[None, :]).sum(1).astype(
        jnp.float32)


def gap(got, want) -> float:
    """Worst |got - want| over the query, relative to the larger of |want|
    and the query's best reference score."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = np.maximum(np.abs(want), np.abs(want).max())
    return float(np.max(np.abs(got - want) / np.maximum(scale, 1e-30)))
