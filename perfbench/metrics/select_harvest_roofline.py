"""The select_harvest kernel family's share of its roofline.

One call a step reads every cell's priority (f32) and valid flag (bool) to
find each row's top-k: ``R*C*5`` bytes a step."""
from perfbench.kernel_bytes import shapes, share


def chunk_bytes(crawl: dict, chips: int) -> float:
    s = shapes(crawl, chips)
    return s["steps"] * s["R"] * s["C"] * 5.0


def read(rec):
    return share(rec, "select_harvest", chunk_bytes(rec.crawl_cfg, rec.chips))
