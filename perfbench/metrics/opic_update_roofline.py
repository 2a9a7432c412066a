"""The opic_update kernel family's share of its roofline.

The cell scatter runs two give-back calls a step over the popped items and
one placement a dispatch over the arrivals, and reads each item's flat cell
index, value and mask: 9 bytes an item."""
from perfbench.kernel_bytes import shapes, share


def chunk_bytes(crawl: dict, chips: int) -> float:
    s = shapes(crawl, chips)
    return (2 * s["steps"] * s["R"] * s["k"] + s["R"] * s["M"]) * 9.0


def read(rec):
    return share(rec, "opic_update", chunk_bytes(rec.crawl_cfg, rec.chips))
