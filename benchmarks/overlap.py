"""C1/C2 + the bandwidth axis — overlap, coverage, quality, and
communication per coordination mode x partitioning scheme (the paper's
central quality claims, §III/§IV, plus the firewall / cross-over / exchange
trade-off WebParF builds on).

Schemes only differ when URLs actually cross shards, so each point runs on 8
virtual shards in a subprocess; the URL space is kept dense (2^18) so alias
collisions (content duplication) actually occur within the crawl horizon.
The partitioning axis iterates the REGISTRY (core/partitioner.policies()),
so third-party policies get raced too: name the module(s) that register
them in ``WEBPARF_PLUGINS`` (comma-separated import paths) — both this
process and every measurement subprocess import them before resolving
policy names, so registration reaches the child where the crawl runs.

``--smoke`` shrinks the grid and the web to CI size (a liveness check, not
a measurement; wired into the CI smoke step alongside benchmarks/run.py's
SUITES entry).
"""
from __future__ import annotations

import sys
import textwrap

from benchmarks.crawl_common import CPU_CHILD_NOTE, run_cpu_child

CHILD = textwrap.dedent("""
    import os, sys, json, importlib
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    sys.path.insert(0, "src"); sys.path.insert(0, ".")
    for _m in filter(None, os.environ.get("WEBPARF_PLUGINS", "").split(",")):
        importlib.import_module(_m)   # third-party policy registration
    import numpy as np
    from repro.api import CrawlSession
    from repro.configs import get_arch
    from repro.configs.base import scaled
    cfg = scaled(get_arch("webparf")[0], dispatch_interval=2,
                 alias_fraction=0.2, partitioning=%(scheme)r,
                 coordination=%(coord)r, comm_quota=%(quota)d,
                 **%(cfg_kw)r)
    rep = CrawlSession(cfg, classify_accuracy=%(acc)f).run(%(steps)d)
    q = rep.ordering_quality
    print(json.dumps(dict(
        m=rep.overlap, comm=rep.comm, mass=q["importance_mass"],
        unique=q["unique_pages"], bloom=rep.stats["dedup_bloom"],
        exact=rep.stats["dedup_exact"], foreign=rep.stats["fetch_foreign"],
        fetched_stat=rep.stats["fetched"])))
""")

FULL_CFG = dict(n_domains=32, frontier_capacity=512, fetch_batch=32,
                bloom_bits_log2=14, dispatch_capacity=2048,
                url_space_log2=18)
SMOKE_CFG = dict(n_domains=16, frontier_capacity=128, fetch_batch=16,
                 outlinks_per_page=8, bloom_bits_log2=13,
                 dispatch_capacity=512, url_space_log2=16,
                 seed_urls_per_domain=8)


def point(scheme: str, acc: float, *, coord: str = "exchange",
          quota: int = -1, steps: int = 64, cfg_kw=None) -> dict:
    src = CHILD % dict(scheme=scheme, acc=acc, coord=coord, quota=quota,
                       steps=steps, cfg_kw=cfg_kw or FULL_CFG)
    return run_cpu_child(src)


def _row(label1, label2, rec):
    m = rec["m"]
    foreign = 100 * rec["foreign"] / max(rec["fetched_stat"], 1)
    print(f"{label1:9s} {label2:>9s} {m['fetched']:8d} {rec['unique']:7d} "
          f"{100 * m['url_dup']:9.3f} {100 * m['content_dup']:13.3f} "
          f"{foreign:9.2f} {rec['mass']:9.1f} "
          f"{rec['comm']['urls_shipped']:8d} "
          f"{rec['comm']['comm_per_page']:7.2f} "
          f"{rec['comm']['urls_dropped']:7d} {rec['comm']['urls_deferred']:7d}")


_HDR = (f"{'':9s} {'':>9s} {'fetched':>8s} {'unique':>7s} {'url_dup%':>9s} "
        f"{'content_dup%':>13s} {'foreign%':>9s} {'imp.mass':>9s} "
        f"{'shipped':>8s} {'c/page':>7s} {'dropped':>7s} {'defer':>7s}")


def main(smoke: bool = False):
    import importlib
    import os
    for m in filter(None, os.environ.get("WEBPARF_PLUGINS", "").split(",")):
        importlib.import_module(m)    # register third-party policies here too
    from repro.coordination import coordinations
    from repro.core import partitioner as PT

    cfg_kw = SMOKE_CFG if smoke else FULL_CFG
    steps = 16 if smoke else 64
    quota = cfg_kw["dispatch_capacity"] // 8   # a real bound for "batched"
    schemes = PT.policies()                    # registry, not a hardcoded tuple

    # -- coordination-mode x partitioning race --------------------------------
    rows = []
    parts = ("webparf",) if smoke else schemes
    for coord in coordinations():
        for scheme in parts:
            q = quota if coord == "batched" else -1
            rows.append((coord, scheme,
                         point(scheme, 0.9, coord=coord, quota=q,
                               steps=steps, cfg_kw=cfg_kw)))
    print(f"\n== coordination mode x partitioning: overlap / coverage / "
          f"quality / bandwidth (8 {CPU_CHILD_NOTE}, {steps} steps, "
          f"batched quota={quota}) ==")
    print(_HDR)
    for coord, scheme, rec in rows:
        _row(coord, scheme, rec)
    print("(firewall/crossover ship 0 URLs: firewall pays in coverage "
          "[unique/imp.mass], crossover pays in C1/C2 overlap; batched "
          "bounds c/page and parks the overflow in the outbox)")

    # -- batched at quota infinity must match exchange ------------------------
    ex = next(r for c, s, r in rows if (c, s) == ("exchange", "webparf"))
    binf = point("webparf", 0.9, coord="batched", quota=-1, steps=steps,
                 cfg_kw=cfg_kw)
    same = binf["m"]["fetched"] == ex["m"]["fetched"] and \
        binf["comm"]["urls_shipped"] == ex["comm"]["urls_shipped"]
    print(f"  batched@quota=inf vs exchange: fetched "
          f"{binf['m']['fetched']} vs {ex['m']['fetched']}, shipped "
          f"{binf['comm']['urls_shipped']} vs "
          f"{ex['comm']['urls_shipped']} "
          f"({'OK' if same else 'REGRESSION'}: an unbounded quota is the "
          f"full exchange)")

    if smoke:
        return rows

    # -- classifier-accuracy sweep (webparf, exchange) ------------------------
    acc_rows = [("webparf", acc, point("webparf", acc, steps=steps,
                                       cfg_kw=cfg_kw))
                for acc in (1.0, 0.7, 0.5)]
    print("\n== C1/C2: overlap by classifier accuracy "
          f"(webparf/exchange, 8 {CPU_CHILD_NOTE}, {steps} steps) ==")
    print(_HDR)
    for scheme, acc, rec in acc_rows:
        _row(scheme, f"acc={acc:.2f}", rec)
    print("(webparf: canonicalization folds aliases before dispatch -> lower "
          "content dup; random assignment has no stable owner -> URL dup)")
    return rows


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv[1:])
