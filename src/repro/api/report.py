"""Typed crawl reports + the host-side metric helpers every driver shares.

``CrawlReport`` is what :meth:`repro.api.CrawlSession.run` returns — the
fetched URLs, per-step fetch counts, the cumulative stat counters, wall time,
and the paper's C1/C2 overlap metrics, in one typed object instead of the
ad-hoc tuples each benchmark used to rebuild. ``stats_dict`` /
``overlap_metrics`` moved here from benchmarks/crawl_common.py (which now
re-exports them); ``harvest`` is the one place device ``FetchReport``s are
unpacked to host numpy, for both eager single-step reports (2-D leaves) and
fused scan chunks (3-D leaves with a leading time axis).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Tuple

import numpy as np

# STATS lives in core/stages.py (its home since the stage split); drivers
# should read counters through here, not via the crawler re-export.
from repro.core.stages import STATS, FetchReport


def stats_dict(state) -> Dict[str, int]:
    """Sum the per-shard stat counters into one named dict, plus the
    frontier's own event counters (FIFO tie-break rebases)."""
    s = np.asarray(state.stats).sum(0)
    out = {n: int(v) for n, v in zip(STATS, s)}
    out["fifo_rebase"] = int(np.asarray(state.f_rebased).sum())
    return out


def stats_per_shard(state) -> Dict[str, np.ndarray]:
    """The per-shard breakdown of :func:`stats_dict`: each counter as an
    ``(n_shards,)`` int64 vector (summing a vector recovers the summed
    dict's entry). The skew between lanes is the load-imbalance signal the
    telemetry layer tracks over time; this is the end-of-run view."""
    s = np.asarray(state.stats).astype(np.int64)
    out = {n: s[:, i].copy() for i, n in enumerate(STATS)}
    n_shards = s.shape[0]
    out["fifo_rebase"] = np.asarray(state.f_rebased).astype(
        np.int64).reshape(n_shards, -1).sum(1)
    return out


def overlap_metrics(urls: np.ndarray, cfg) -> Dict[str, float]:
    """C1 (URL) and C2 (content) overlap over a fetched-URL trace."""
    import jax.numpy as jnp

    from repro.core import webgraph as W
    if len(urls) == 0:
        return dict(url_dup=0.0, content_dup=0.0, fetched=0)
    canon = np.asarray(W.canonical(jnp.asarray(urls.astype(np.uint32)), cfg))
    return dict(
        fetched=len(urls),
        url_dup=1.0 - len(np.unique(urls)) / len(urls),
        content_dup=1.0 - len(np.unique(canon)) / len(canon),
    )


def harvest(rep: FetchReport) -> Tuple[List[np.ndarray], List[int]]:
    """Unpack a FetchReport to ([fetched urls per step], [count per step]).

    Accepts one eager step's report ((n_slots, k) leaves) or a fused chunk's
    stacked report ((steps, n_slots, k) leaves) — one device transfer either
    way, which is the point of the scan path."""
    m = np.asarray(rep.fetched_mask)
    u = np.asarray(rep.fetched_urls)
    if m.ndim == 2:
        m, u = m[None], u[None]
    return [u[t][m[t]] for t in range(m.shape[0])], \
           [int(mt.sum()) for mt in m]


@dataclasses.dataclass(frozen=True)
class CrawlReport:
    """What one ``CrawlSession.run`` produced (host-side, numpy)."""
    urls: np.ndarray                     # fetched URL ids in crawl order
    per_step: np.ndarray                 # (steps,) pages fetched per step
    stats: Dict[str, int]                # cumulative counters at run end
    seconds: float                       # wall time of the run
    cfg: Any = dataclasses.field(default=None, repr=False, compare=False)
    stats_per_shard: Dict[str, np.ndarray] = dataclasses.field(
        default=None, repr=False, compare=False)   # per-shard counter lanes
    telemetry: Any = dataclasses.field(
        default=None, repr=False, compare=False)   # obs.health.CrawlTelemetry
                                                   # (None with telemetry off)
    rebalances: Tuple = dataclasses.field(
        default=(), repr=False, compare=False)     # RebalanceEvents applied
                                                   # during this run (elastic
                                                   # repartitioning,
                                                   # DESIGN.md §18)

    @functools.cached_property
    def overlap(self) -> Dict[str, float]:
        """C1/C2 metrics over this run's URLs — computed on first access, so
        segmented drivers that only read ``.urls`` never pay for it."""
        if self.cfg is None:
            return dict(url_dup=0.0, content_dup=0.0, fetched=0)
        return overlap_metrics(self.urls, self.cfg)

    @functools.cached_property
    def ordering_quality(self) -> Dict[str, float]:
        """Ordering-quality metrics (repro/ordering/quality.py): importance-
        weighted coverage of the fetched pages, how front-loaded it was
        (AUC), and hub-page counts. Lazy like ``overlap``."""
        from repro.ordering.quality import ordering_quality
        if self.cfg is None:
            return {}
        return ordering_quality(self.urls, self.per_step, self.cfg)

    @functools.cached_property
    def comm(self) -> Dict[str, float]:
        """The communication-budget ledger (repro/coordination/metrics.py):
        URLs shipped / crossed / received / dropped / deferred by the
        coordination mode, and the paper's bandwidth metric — shipped URLs
        per fetched page, and crossed (bound for another shard) per page.
        Zero-communication modes (firewall, crossover) report
        ``comm_per_page == 0``."""
        from repro.coordination.metrics import comm_ledger
        return comm_ledger(self.stats, self.fetched)

    @property
    def steps(self) -> int:
        return len(self.per_step)

    @property
    def fetched(self) -> int:
        return int(self.per_step.sum())

    @property
    def pages_per_sec(self) -> float:
        return self.fetched / max(self.seconds, 1e-9)

    def summary(self) -> str:
        line = (f"{self.fetched} pages / {self.steps} steps in "
                f"{self.seconds:.2f}s ({self.pages_per_sec:.0f} pages/s)")
        if self.overlap and self.overlap["fetched"]:
            line += (f", url_dup {100 * self.overlap['url_dup']:.2f}%"
                     f", content_dup {100 * self.overlap['content_dup']:.2f}%")
        if self.rebalances:
            moved = sum(len(e.moves) for e in self.rebalances)
            line += (f", {len(self.rebalances)} rebalances "
                     f"({moved} domains migrated)")
        return line
