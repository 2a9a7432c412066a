"""Open-loop search traffic on the wall clock.

The arithmetic of the program's step-clock generator (a Poisson count per
time unit at seeded uniform times, a Zipf topic mix over the domains,
seeded and seekable) with one second as the unit, so the offered load is
the same whatever speed the crawl runs at. The counts of each block of
``block_s`` seconds are drawn once for all seeds, and each seed puts them
in an order of its own: a second's count is Poisson, and a seed changes
which second gets which count, when in it the queries come and what they
ask, but not how many a block holds, so two seeds offer the same work.
``take`` has the shape the
program's ``ServeSession`` asks of its load: it hands out every query whose
wall due time has passed, and stamps them with the session's own clock
value, so the session times them from the moment it takes them; the time
between due and take is kept here and added back.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

_COUNT_SALT = 0x636E       # the counts of a block, the same for every seed
_ORDER_SALT = 0x6F72       # a seed's order of them


@dataclass(frozen=True)
class Batch:
    time: np.ndarray         # the session's clock value at the take
    domain: np.ndarray       # (n,) int32 query topic
    seed: np.ndarray         # (n,) uint32 query text seed
    cursor: int

    def __len__(self) -> int:
        return len(self.domain)


def zipf_probs(n_domains: int, zipf_q: float) -> np.ndarray:
    w = np.arange(1, n_domains + 1, dtype=np.float64) ** -float(zipf_q)
    return w / w.sum()


class WallLoad:
    """Queries due at ``origin + t`` for a schedule ``t`` drawn from ``seed``."""

    def __init__(self, *, rate: float, n_domains: int, seed: int,
                 zipf_q: float = 1.1, block_s: int = 4,
                 clock=time.perf_counter):
        if rate < 0:
            raise ValueError(f"rate must be >= 0, got {rate}")
        self.rate = float(rate)
        self.seed = int(seed) % (1 << 64)
        self.n_domains = int(n_domains)
        self.probs = zipf_probs(n_domains, zipf_q)
        self.block_s = max(int(block_s), 1)
        self.clock = clock
        self.origin = None
        self._due = np.empty(0)
        self._domain = np.empty(0, np.int32)
        self._qseed = np.empty(0, np.uint32)
        self._units = 0
        self.warm: List[Tuple[np.ndarray, np.ndarray]] = []
        self.takes: List[Tuple[float, int, int]] = []   # (wall, lo, hi)

    # -- the schedule ---------------------------------------------------------

    def counts(self, block: int) -> np.ndarray:
        """The query count of each second of a block, in this seed's order."""
        n = np.random.default_rng([_COUNT_SALT, block]).poisson(
            self.rate, self.block_s)
        order = np.random.default_rng([self.seed, _ORDER_SALT, block])
        return n[order.permutation(self.block_s)]

    def _unit(self, unit: int):
        rng = np.random.default_rng([self.seed, unit])
        n = int(self.counts(unit // self.block_s)[unit % self.block_s])
        t = unit + np.sort(rng.random(n))
        dom = rng.choice(self.n_domains, size=n, p=self.probs).astype(np.int32)
        qs = rng.integers(1, 1 << 31, size=n, dtype=np.int64).astype(np.uint32)
        return t, dom, qs

    def schedule(self, through_s: float) -> int:
        """Materialise the schedule through ``through_s`` seconds; returns
        how many queries are due by then."""
        while self._units <= int(np.ceil(through_s)):
            t, dom, qs = self._unit(self._units)
            self._due = np.concatenate([self._due, t])
            self._domain = np.concatenate([self._domain, dom])
            self._qseed = np.concatenate([self._qseed, qs])
            self._units += 1
        return int(np.searchsorted(self._due, through_s, side="right"))

    def n_due(self, wall: float, inclusive: bool = True) -> int:
        """How many queries are due by ``wall`` (at or before it, or only
        before it)."""
        t = wall - self.origin
        self.schedule(t)
        return int(np.searchsorted(self._due, t,
                                   side="right" if inclusive else "left"))

    def due(self, lo: int, hi: int) -> np.ndarray:
        """Wall due times (same clock as ``clock``) of queries lo..hi."""
        return self.origin + self._due[lo:hi]

    def queries(self, lo: int, hi: int) -> Tuple[np.ndarray, np.ndarray]:
        return self._qseed[lo:hi], self._domain[lo:hi]

    # -- the session's side -----------------------------------------------------

    def start(self, origin: float) -> None:
        """Due time 0 of the schedule is ``origin`` on ``clock``."""
        self.origin = float(origin)

    def add_warm(self, n: int, seed_base: int = 1) -> None:
        """Queue ``n`` queries for the next take before ``start``: they
        compile the query path during set-up."""
        seeds = (np.arange(n, dtype=np.uint32) + np.uint32(seed_base))
        self.warm.append((seeds, np.arange(n, dtype=np.int32)
                          % self.n_domains))

    def take(self, cursor: int, t_now: float) -> Batch:
        now = self.clock()
        stamp = np.float64(t_now)
        if self.origin is None:
            seeds, doms = self.warm.pop(0) if self.warm else (
                np.empty(0, np.uint32), np.empty(0, np.int32))
            return Batch(np.full(len(seeds), stamp), doms, seeds, cursor)
        hi = self.schedule(now - self.origin)
        lo = min(cursor, hi)
        self.takes.append((now, lo, hi))
        return Batch(np.full(hi - lo, stamp), self._domain[lo:hi].copy(),
                     self._qseed[lo:hi].copy(), hi)
