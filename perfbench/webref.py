"""The synthetic web, written out plainly in numpy for the reference.

A copy of the program's hash arithmetic (URL layout, outlinks, aliases,
page tokens, hub seeds, the dispatcher's domain prediction), kept here so
that the reference that decides ``correct`` imports nothing of the program.
Every function is uint32 arithmetic or a float32 comparison, written so that
numpy rounds exactly as the device does.
"""
from __future__ import annotations

import numpy as np

U32 = np.uint32
_M32 = 0xFFFFFFFF


def _u32(x) -> np.ndarray:
    return np.asarray(x).astype(U32)


def mix(x, salt: int) -> np.ndarray:
    """murmur3-style finalizer on uint32."""
    x = _u32(x) ^ U32((salt * 0x9E3779B9 + 0x85EBCA6B) & _M32)
    with np.errstate(over="ignore"):
        x = (x ^ (x >> U32(16))) * U32(0x85EBCA6B)
        x = (x ^ (x >> U32(13))) * U32(0xC2B2AE35)
    return x ^ (x >> U32(16))


def hash2(a, b, salt: int = 0) -> np.ndarray:
    with np.errstate(over="ignore"):
        return mix(_u32(a) + mix(_u32(b), salt + 7), salt)


def uniform(h) -> np.ndarray:
    """uint32 -> float32 in [0, 1), rounded as the device rounds."""
    return _u32(h).astype(np.float32) * np.float32(1.0 / 4294967296.0)


class Web:
    """The web of one configuration (a dict of ``CrawlConfig`` fields)."""

    def __init__(self, cfg: dict):
        self.n_domains = int(cfg["n_domains"])
        self.local_bits = int(cfg["url_space_log2"]) - int(
            np.log2(self.n_domains))
        self.alias_fraction = float(cfg["alias_fraction"])
        self.topical_locality = float(cfg["topical_locality"])
        self.outlinks_per_page = int(cfg["outlinks_per_page"])
        self.seed_urls = int(cfg["seed_urls_per_domain"])
        if float(cfg.get("link_pop_bias", 0.0)) != 0.0:
            raise ValueError("the reference web has no link popularity bias")
        w = 1.0 / np.arange(1, self.n_domains + 1) ** float(cfg["zipf_a"])
        w = w / w.sum()
        self.cumw = np.cumsum(w).astype(np.float32)
        lb = self.local_bits
        self.alias_start = U32(int((1 << lb) * (1.0 - self.alias_fraction)))

    def domain_of(self, url) -> np.ndarray:
        return (_u32(url) >> U32(self.local_bits)).astype(np.int32)

    def make_url(self, domain, local) -> np.ndarray:
        mask = U32((1 << self.local_bits) - 1)
        return (_u32(domain) << U32(self.local_bits)) | (_u32(local) & mask)

    def canonical(self, url) -> np.ndarray:
        url = _u32(url)
        local = url & U32((1 << self.local_bits) - 1)
        canon = mix(local, 11) % max(self.alias_start, U32(1))
        return np.where(local >= self.alias_start,
                        self.make_url(self.domain_of(url), canon), url
                        ).astype(U32)

    def sample_domain(self, h) -> np.ndarray:
        return np.searchsorted(self.cumw, uniform(h), side="left"
                               ).astype(np.int32)

    def outlinks(self, url) -> np.ndarray:
        """(n,) -> (n, outlinks_per_page) discovered URLs."""
        url = _u32(url)
        c = self.canonical(url)[:, None]
        i = np.arange(self.outlinks_per_page, dtype=U32)[None, :]
        stay = uniform(hash2(c, i, 1)) < np.float32(self.topical_locality)
        dom = np.where(stay, self.domain_of(url)[:, None],
                       self.sample_domain(hash2(c, i, 2)))
        return self.make_url(dom, hash2(c, i, 3))

    def popularity(self, url) -> np.ndarray:
        u = uniform(mix(self.canonical(url), 21))
        return np.float32(1.0) - np.sqrt(u)

    def hub_seeds(self) -> np.ndarray:
        """(n_domains, seed_urls) — the most popular of a hashed window of
        candidate URLs per domain, as the Phase I seed gathering picks."""
        d = np.arange(self.n_domains, dtype=U32)[:, None]
        n_cand = max(self.seed_urls * 8, 64)
        local = mix(hash2(d, np.arange(n_cand, dtype=U32)[None, :], 31), 32)
        cand = self.make_url(np.broadcast_to(d, local.shape), local)
        order = np.argsort(-self.popularity(cand), axis=1, kind="stable")
        return np.take_along_axis(cand, order[:, :self.seed_urls], axis=1)

    def predict_domain(self, url, src_domain, step: int,
                       accuracy: float) -> np.ndarray:
        u = uniform(hash2(url, U32(step), 51))
        return np.where(u < np.float32(accuracy), self.domain_of(url),
                        np.asarray(src_domain, np.int32)).astype(np.int32)

    def page_tokens(self, url, n_tokens: int, vocab: int) -> np.ndarray:
        """(n,) -> (n, n_tokens) int32 hashed terms of each page."""
        c = self.canonical(url)[:, None]
        i = np.arange(n_tokens, dtype=U32)[None, :]
        h = hash2(c, i, 4)
        dom = self.domain_of(url)[:, None]
        band = vocab // max(self.n_domains, 1)
        in_band = uniform(hash2(c, i, 5)) < np.float32(0.7)
        tok_band = dom * band + (h % U32(max(band, 1))).astype(np.int32)
        tok_glob = (h % U32(vocab)).astype(np.int32)
        return np.where(in_band, tok_band, tok_glob).astype(np.int32)

    def query_terms(self, seed, domain, n_terms: int, vocab: int
                    ) -> np.ndarray:
        """(n,) seeds and domains -> (n, n_terms) query terms."""
        band = vocab // max(self.n_domains, 1)
        i = np.arange(n_terms, dtype=U32)[None, :]
        h = hash2(_u32(seed)[:, None], i, 91)
        return (np.asarray(domain, np.int32)[:, None] * band
                + (h % U32(max(band, 1))).astype(np.int32)).astype(np.int32)
