"""What a kernel family's roofline share is built from.

Each ``metrics/<family>_roofline.py`` counts the least HBM bytes its family
must move in one crawl chunk, from the operand shapes that the
configuration fixes (``shapes``), per chip and the same whichever
implementation runs: what any implementation has to read to produce the
result, with every write made in place. ``share`` sets the least time those
bytes take at the chip's HBM bandwidth against the family's device time
inside its ``kernel/<family>.<impl>`` scope, so the share cannot pass 100%.

Per chunk of ``steps`` = ``dispatch_interval`` steps: ``R`` frontier rows on
the chip, ``C`` cells per row, ``k`` pops per row and ``M`` dispatch
arrivals per row.
"""
from __future__ import annotations

from perfbench.peaks import peaks


def shapes(crawl: dict, chips: int) -> dict:
    R = crawl["n_domains"] * crawl["slot_factor"] // chips
    C = crawl["frontier_capacity"]
    k = max(1, crawl["fetch_batch"] // R)
    S = crawl["dispatch_capacity"]
    cap_ex = max(8, -(-S // chips) * 2)
    M = min(chips * cap_ex, C)
    return dict(R=R, C=C, k=k, M=M, steps=crawl["dispatch_interval"])


def share(rec, family: str, chunk_bytes: float):
    """Percent of the family's roofline over the traced chunks, or None
    where the trace holds no time in its scope."""
    t = rec.trace
    if t is None or not rec.traced_calls:
        return None
    s = t.scope_s(f"kernel/{family}.")
    if s <= 0:
        return None
    least = rec.traced_calls * chunk_bytes / peaks(rec.device_kind)["hbm_bw"]
    return 100.0 * least / s
