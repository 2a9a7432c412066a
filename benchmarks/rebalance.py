"""Load-driven elastic repartitioning (DESIGN.md §18) — static vs elastic
on a deliberately skewed web.

A Zipf-skewed, preferential-attachment web (high ``zipf_a``, full
``link_pop_bias``, lowered ``topical_locality``) piles frontier depth onto
the shard owning the head domains; the static WebParF assignment rides the
pile-up to the end while the elastic arm lets the ledger trigger migrate
hot domains off the peak shard mid-crawl. Each arm runs on 4 virtual
shards in a subprocess and reports the per-interval load-imbalance series
(max/mean over live shards of frontier depth), coverage (unique pages),
bandwidth, the migration count, and total ordering cash before/after —
the verdict asserts the elastic arm cuts MAX imbalance by >=30% at
near-equal coverage with cash conserved exactly.

The max is taken past a 2-record warm-up in BOTH arms: one interval to
observe the skew, one for the cascade to settle (the head domain's new
home must itself shed load) — the reaction-latency floor no control loop
can beat. The raw first-record peak is identical by construction and
reported alongside.

``--smoke`` shrinks the web/horizon to a CI liveness check (wired into the
tier-1 step; the full race persists as BENCH_rebalance.json through
benchmarks/run.py).
"""
from __future__ import annotations

import sys
import textwrap

import numpy as np

from benchmarks.crawl_common import CPU_CHILD_NOTE, run_cpu_child

CHILD = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, "src"); sys.path.insert(0, ".")
    import numpy as np
    from repro.api import CrawlSession
    from repro.configs import get_arch
    from repro.configs.base import scaled
    from repro.ordering import total_cash
    cfg = scaled(get_arch("webparf")[0], ordering="opic_url",
                 telemetry=True, dispatch_interval=2, link_pop_bias=1.0,
                 zipf_a=%(zipf)f, topical_locality=%(loc)f,
                 rebalance_threshold=%(thr)f, rebalance_window=1,
                 rebalance_max_domains=%(maxd)d, **%(cfg_kw)r)
    sess = CrawlSession(cfg)
    c0 = float(total_cash(sess.state))
    rep = sess.run(%(steps)d)
    tel = rep.telemetry.per_interval()
    imb = tel.imbalance()
    q = rep.ordering_quality
    print(json.dumps(dict(
        imb_series=[round(float(x), 4) for x in imb],
        imb_mean=float(imb.mean()), imb_final=float(imb[-1]),
        unique=q["unique_pages"], fetched=rep.stats["fetched"],
        comm_per_page=rep.comm["comm_per_page"],
        shipped=rep.comm["urls_shipped"],
        cash0=c0, cash1=float(total_cash(sess.state)),
        n_rebalances=len(rep.rebalances),
        domains_moved=sum(len(e.domains) for e in rep.rebalances))))
""")

FULL_CFG = dict(n_domains=32, frontier_capacity=2048, fetch_batch=32,
                bloom_bits_log2=14, dispatch_capacity=2048,
                url_space_log2=18)
SMOKE_CFG = dict(n_domains=16, frontier_capacity=256, fetch_batch=16,
                 outlinks_per_page=8, bloom_bits_log2=13,
                 dispatch_capacity=512, url_space_log2=16,
                 seed_urls_per_domain=8)

# records excluded from the max in both arms (reaction-latency floor)
WARMUP = 2


def point(*, thr: float, steps: int, cfg_kw: dict, zipf: float = 1.35,
          loc: float = 0.5, maxd: int = 6) -> dict:
    src = CHILD % dict(thr=thr, steps=steps, cfg_kw=cfg_kw, zipf=zipf,
                       loc=loc, maxd=maxd)
    rec = run_cpu_child(src)
    series = rec["imb_series"]
    rec["imb_max_raw"] = max(series)
    rec["imb_max"] = max(series[WARMUP:] or series)
    return rec


def _row(label: str, rec: dict) -> None:
    print(f"{label:9s} {rec['imb_max']:8.2f} {rec['imb_max_raw']:8.2f} "
          f"{rec['imb_mean']:9.2f} "
          f"{rec['imb_final']:9.2f} {rec['unique']:7d} {rec['fetched']:8d} "
          f"{rec['comm_per_page']:7.2f} {rec['n_rebalances']:5d} "
          f"{rec['domains_moved']:6d}")


_HDR = (f"{'':9s} {'imb_max':>8s} {'imb_raw':>8s} {'imb_mean':>9s} "
        f"{'imb_final':>9s} "
        f"{'unique':>7s} {'fetched':>8s} {'c/page':>7s} {'rebal':>5s} "
        f"{'moved':>6s}")


def main(smoke: bool = False):
    cfg_kw = SMOKE_CFG if smoke else FULL_CFG
    steps = 16 if smoke else 96
    thr = 1.15

    static = point(thr=0.0, steps=steps, cfg_kw=cfg_kw)
    elastic = point(thr=thr, steps=steps, cfg_kw=cfg_kw)

    print(f"\n== elastic repartitioning on a Zipf-skewed web "
          f"(4 {CPU_CHILD_NOTE}, {steps} steps, trigger threshold {thr}) ==")
    print(_HDR)
    _row("static", static)
    _row("elastic", elastic)

    for label, rec in (("static", static), ("elastic", elastic)):
        assert np.isclose(rec["cash0"], rec["cash1"], rtol=1e-4), \
            (label, "OPIC cash not conserved", rec["cash0"], rec["cash1"])
    print(f"  cash conserved: static {static['cash1']:.4f} / elastic "
          f"{elastic['cash1']:.4f} (both == init, rtol 1e-4)")
    assert static["n_rebalances"] == 0, "static arm migrated"

    cut = 1.0 - elastic["imb_max"] / max(static["imb_max"], 1e-9)
    cov = elastic["unique"] / max(static["unique"], 1)
    ok = (not smoke and elastic["n_rebalances"] > 0
          and cut >= 0.30 and cov >= 0.9)
    verdict = "OK" if ok else ("SMOKE" if smoke else "REGRESSION")
    print(f"  verdict: elastic max imbalance {elastic['imb_max']:.2f} vs "
          f"static {static['imb_max']:.2f} (-{100 * cut:.0f}%, need >=30%, "
          f"past {WARMUP}-record warm-up) "
          f"at {100 * cov:.0f}% coverage, {elastic['n_rebalances']} "
          f"migrations [{verdict}]")
    if not smoke:
        assert ok, "elastic arm failed the imbalance/coverage bar"

    return dict(steps=steps, threshold=thr, static=static, elastic=elastic,
                imbalance_cut=round(cut, 4), coverage_ratio=round(cov, 4))


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv[1:])
