"""The dedup_deposit kernel family's share of its roofline.

One call a dispatch reads the arrivals' URLs and masks (``R*M*5`` bytes)
and the queued URLs and valid flags they are matched against
(``R*C*5``)."""
from perfbench.kernel_bytes import shapes, share


def chunk_bytes(crawl: dict, chips: int) -> float:
    s = shapes(crawl, chips)
    return s["R"] * s["M"] * 5.0 + s["R"] * s["C"] * 5.0


def read(rec):
    return share(rec, "dedup_deposit", chunk_bytes(rec.crawl_cfg, rec.chips))
