"""C3 — throughput scaling with crawl processes + domain sub-splitting.

Shards are virtual host devices, so each point runs in a subprocess with its
own --xla_force_host_platform_device_count.
"""
from __future__ import annotations

import textwrap

from benchmarks.crawl_common import CPU_CHILD_NOTE, run_cpu_child

CHILD = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
    sys.path.insert(0, "src"); sys.path.insert(0, ".")
    import numpy as np
    from repro.configs import get_arch
    from repro.configs.base import scaled
    from benchmarks.crawl_common import run_crawl, stats_dict
    cfg = scaled(get_arch("webparf")[0], n_domains=%d, frontier_capacity=512,
                 fetch_batch=%d, bloom_bits_log2=14, dispatch_capacity=2048,
                 url_space_log2=24)
    urls, state, per_step, dt = run_crawl(cfg, 32)
    print(json.dumps(dict(n=%d, fetched=len(urls), steady=float(per_step[8:].mean()),
                          wall=dt)))
""")


def point(n_shards, n_domains, fetch_batch):
    src = CHILD % (n_shards, n_domains, fetch_batch, n_shards)
    return run_cpu_child(src)


def main():
    print(f"\n== C3: crawl throughput vs parallel crawl processes "
          f"({CPU_CHILD_NOTE}) ==")
    print(f"{'shards':>7s} {'domains':>8s} {'fetched(32 steps)':>18s} "
          f"{'steady pages/step':>18s}")
    base = None
    # per-shard fetch width held constant -> ideal scaling doubles pages/step
    for n in (1, 2, 4, 8):
        rec = point(n, 32, 8 * 32 // max(n, 1) * n // 32 or 8)
        rec = point(n, 32, 8)
        if base is None:
            base = rec["steady"] or 1.0
        print(f"{n:7d} {32:8d} {rec['fetched']:18d} {rec['steady']:18.1f}"
              f"   ({rec['steady']/base:.2f}x)")
    # C3b: sub-domain split doubles partitions at same shard count
    print("\n-- domain split (32 -> 64 domains, 4 shards) --")
    for nd in (32, 64):
        rec = point(4, nd, 8)
        print(f"  domains={nd:3d}: steady {rec['steady']:.1f} pages/step")


if __name__ == "__main__":
    main()
