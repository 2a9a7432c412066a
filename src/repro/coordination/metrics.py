"""The COMMUNICATION-BUDGET LEDGER — the paper's bandwidth axis, measured.

WebParF frames URL distribution as a four-way trade-off (overlap, coverage,
quality, communication bandwidth); the first three have had metrics since
the overlap/ordering benchmarks — this module supplies the fourth. All
counters come from the crawl's own stat row (core/stages.STATS), summed by
``repro.api.report.stats_dict``:

  urls_shipped   — URLs handed to the all_to_all (``dispatch_sent``),
                   including the self-bound URLs exchange mode ships back
                   to their own sender, so not the bandwidth spent.
  urls_crossed   — shipped URLs bound for another process
                   (``dispatch_foreign``): the inter-process bandwidth
                   actually spent; 0 on one shard.
  urls_received  — URLs entering the local insert path (``dispatch_recv``;
                   for zero-communication modes these are kept-local URLs).
  urls_dropped   — URLs a coordination policy discarded (firewall's foreign
                   drops, outbox overflow): the coverage paid for silence.
  urls_deferred  — URLs parked in the outbox for a later dispatch
                   (cumulative over rounds; a URL parked twice counts
                   twice — it occupied budget-decision space twice).
  comm_per_page  — shipped URLs per fetched page: the paper's communication
                   overhead metric (Cho & Garcia-Molina report exchange
                   mode at ~constant URLs exchanged per page downloaded;
                   firewall/crossover sit at exactly 0).
  crossed_per_page — crossed URLs per fetched page.

Surfaced as :attr:`repro.api.CrawlReport.comm` and raced mode x
partitioning by benchmarks/overlap.py.
"""
from __future__ import annotations

from typing import Dict


def comm_ledger(stats: Dict[str, int], fetched: int) -> Dict[str, float]:
    """Fold a run's stat counters into the communication ledger."""
    shipped = int(stats.get("dispatch_sent", 0))
    crossed = int(stats.get("dispatch_foreign", 0))
    pages = max(int(fetched), 1)
    return dict(
        urls_shipped=shipped,
        urls_crossed=crossed,
        urls_received=int(stats.get("dispatch_recv", 0)),
        urls_dropped=int(stats.get("coord_dropped", 0)),
        urls_deferred=int(stats.get("coord_deferred", 0)),
        comm_per_page=shipped / pages,
        crossed_per_page=crossed / pages,
    )


def ledger_line(comm: Dict[str, float]) -> str:
    """One human line for drivers (launch/crawl.py, benchmarks)."""
    return (f"{comm['urls_shipped']} URLs shipped "
            f"({comm['comm_per_page']:.2f}/page), "
            f"{comm['urls_crossed']} crossed "
            f"({comm['crossed_per_page']:.2f}/page), "
            f"{comm['urls_dropped']} dropped, "
            f"{comm['urls_deferred']} deferred")
