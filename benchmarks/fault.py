"""C4 — fault tolerance: kill a crawl process, rebalance, measure recovery.

Runs on 4 virtual shards in a subprocess.
"""
from __future__ import annotations

import textwrap

from benchmarks.crawl_common import CPU_CHILD_NOTE, run_cpu_child

CHILD = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, "src"); sys.path.insert(0, ".")
    import numpy as np
    from repro.configs import get_arch
    from repro.configs.base import scaled
    from repro.core import crawler as CR
    from repro.train.fault import heal_crawler
    from benchmarks.crawl_common import run_crawl, overlap_metrics

    cfg = scaled(get_arch("webparf")[0], n_domains=32, frontier_capacity=512,
                 fetch_batch=32, bloom_bits_log2=14, dispatch_capacity=2048,
                 url_space_log2=24)
    events = {}
    if %(fail)d >= 0:
        events[%(fail)d] = lambda s: CR.mark_dead(s, [1])
    if %(heal)d >= 0:
        events[%(heal)d] = lambda s: heal_crawler(s, cfg, [1], 4)
    urls, state, per_step, _ = run_crawl(cfg, 48, events=events)
    m = overlap_metrics(urls, cfg)
    phases = dict(
        healthy=float(per_step[4:16].mean()),
        degraded=float(per_step[20:32].mean()),
        recovered=float(per_step[36:48].mean()),
    )
    print(json.dumps(dict(phases=phases, url_dup=m["url_dup"],
                          revived=int(np.asarray(state.stats).sum(0)[11]))))
""")


def run(fail, heal):
    return run_cpu_child(CHILD % dict(fail=fail, heal=heal))


def main():
    print(f"\n== C4: shard failure at step 16 (4 {CPU_CHILD_NOTE}, "
          f"48 steps) ==")
    base = run(-1, -1)
    dead = run(16, -1)
    healed = run(16, 28)
    print(f"{'run':12s} {'healthy':>9s} {'degraded':>9s} {'recovered':>10s} "
          f"{'url_dup%':>9s} {'revived':>8s}")
    for name, rec in [("no-failure", base), ("failure", dead),
                      ("failure+heal", healed)]:
        p = rec["phases"]
        print(f"{name:12s} {p['healthy']:9.1f} {p['degraded']:9.1f} "
              f"{p['recovered']:10.1f} {100*rec['url_dup']:9.3f} "
              f"{rec['revived']:8d}")
    print("(rebalance migrates the dead shard's domain queues to survivors; "
          "pages/step recovers while URL overlap stays ~0 — the paper's C4)")


if __name__ == "__main__":
    main()
