"""Chip smoke: the crawl -> index -> serve path on TPU at deployment widths.

    python3 chip_smoke.py              # one chip (default)
    python3 chip_smoke.py --chips 4    # the four-chip exchange path only

One chip runs ROADMAP deployment A — the ``CrawlConfig`` defaults (256
domains x slot_factor 2 = 512 frontier rows, 4096-deep queues, fetch_batch
64, 16 outlinks, 4 Bloom hashes, a dispatch every 4 steps,
dispatch_capacity 2048) with ``ordering="opic_url"``,
``coordination="exchange"``, the fused dispatch path and
``kernel_impl="auto"``. The one cut is the Bloom filter size: the largest
``bloom_bits_log2`` whose compiled fused chunk fits the chip by
``memory_analysis()``. It then

  (a) crawls under ``CrawlSession.run(mode="scan")`` — two warm-up
      intervals, then the measured ones — and checks no URL was fetched
      twice (C1), OPIC cash is conserved, and the fetched URLs and
      per-step counts equal a ``kernel_impl="ref"`` run of the same
      configuration on the same chip;
  (b) serves query batches from the live index with ``ServeSession`` and
      checks them against the full-index oracle (recall@k).

``--chips 4`` runs 1024 domains over four chips (each chip holds
deployment A's 512 rows, same Bloom cut) through the ``exchange``
all_to_all, against ``kernel_impl="ref"`` on the same four chips, and
shows each device holding its own shard of the state.

Every reading printed is a smoke reading, not a benchmark result. The
script exits non-zero, and prints no result line, when JAX finds no TPU or
any check fails; otherwise the last line of stdout is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``. It starts no
other process.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

FAMILIES = ("frontier_select", "select_harvest", "bloom", "dedup_deposit",
            "opic_update")
# families Mosaic compiles for the chip; the rest resolve to ref there
COMPILED = ("frontier_select", "select_harvest")
DOMAINS_PER_CHIP = 256           # deployment A: 512 frontier rows per chip
WARMUP_INTERVALS = 2             # the fused chunk's compile warm-up
RUN_INTERVALS = 8
SERVE_INTERVALS = 4
CASH_RTOL = 1e-4                 # tests/test_invariants.py's conservation


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  check ok: {what}")


def chunk_bytes(ma) -> int:
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def bloom_cut(cfg, mesh, limit: int, start: int):
    """The largest bloom_bits_log2 <= start whose fused chunk fits."""
    from repro.api.session import chunk_memory
    from repro.configs.base import scaled
    for b in range(start, 0, -1):
        t0 = time.perf_counter()
        need = chunk_bytes(chunk_memory(scaled(cfg, bloom_bits_log2=b), mesh))
        fits = need <= limit
        print(f"bloom_bits_log2={b}: compiled fused chunk needs {need} B per "
              f"device (memory_analysis), limit {limit} B -> "
              f"{'fits' if fits else 'does not fit'} (compile "
              f"{time.perf_counter() - t0:.3f} s)")
        if fits:
            return b, need
    raise SmokeFailure("no Bloom size fits the device")


def crawl(cfg, mesh, devices, label: str):
    """Warm up, then run the measured intervals; returns the trajectory."""
    import jax
    import numpy as np
    from repro.api import CrawlSession
    from repro.ordering import total_cash

    iv = cfg.dispatch_interval
    t0 = time.perf_counter()
    sess = CrawlSession(cfg, mesh)
    jax.block_until_ready(sess.state)
    c0 = total_cash(sess.state)
    warm = sess.run(WARMUP_INTERVALS * iv, mode="scan")
    setup_s = time.perf_counter() - t0
    rep = sess.run(RUN_INTERVALS * iv, mode="scan")
    c1 = total_cash(sess.state)
    urls = np.concatenate([warm.urls, rep.urls])
    per_step = np.concatenate([warm.per_step, rep.per_step])
    kind = devices[0].device_kind
    print(f"[{label}] init + warm-up (compile) {setup_s:.3f} s; "
          f"{rep.fetched} pages in {rep.seconds:.6f} s over "
          f"{RUN_INTERVALS * iv} steps = {rep.fetched / rep.seconds:.3f} "
          f"pages/s on {len(devices)} x {kind} (smoke reading, not a "
          f"benchmark)")
    return dict(urls=urls, per_step=per_step, cash0=c0, cash1=c1,
                stats=rep.stats, sess=sess)


def check_crawl(run, label: str) -> None:
    import numpy as np
    urls = run["urls"]
    dup = len(urls) - len(np.unique(urls))
    print(f"[{label}] fetched {len(urls)} pages, C1 duplicate fetches {dup}, "
          f"cash {run['cash0']!r} -> {run['cash1']!r}")
    check(len(urls) > 0, f"{label}: pages were fetched")
    check(dup == 0, f"{label}: C1 duplicate fetches == 0")
    check(abs(run["cash1"] - run["cash0"]) <= CASH_RTOL * abs(run["cash0"]),
          f"{label}: OPIC cash conserved within rtol {CASH_RTOL}")


def compare_ref(got, ref, label: str) -> None:
    import numpy as np
    check(np.array_equal(got["per_step"], ref["per_step"]),
          f"{label}: per-step fetch counts identical to kernel_impl=ref")
    check(np.array_equal(got["urls"], ref["urls"]),
          f"{label}: fetched URL sequence identical to kernel_impl=ref")


def memory_line(devices) -> None:
    for d in devices:
        st = d.memory_stats() or {}
        print(f"device {d.id} ({d.device_kind}): bytes_in_use "
              f"{st.get('bytes_in_use')}, peak_bytes_in_use "
              f"{st.get('peak_bytes_in_use')}, bytes_limit "
              f"{st.get('bytes_limit')}")


def serve(cfg, mesh) -> None:
    import numpy as np
    from repro.serve import ServeSession
    iv = cfg.dispatch_interval
    sess = ServeSession(cfg, mesh)
    sess.run(WARMUP_INTERVALS * iv)
    rep = sess.run(SERVE_INTERVALS * iv)
    # each interval's arrivals are answered in batches of query_batch
    _, per_iv = np.unique(np.ceil(rep.arrival_step / iv), return_counts=True)
    n_batches = int(sum(-(-c // sess.query_batch) for c in per_iv))
    print(f"[serve] {rep.n_queries} queries in {n_batches} batches over "
          f"{SERVE_INTERVALS * iv} crawl steps; p50 {rep.p50_ms:.3f} ms, "
          f"p99 {rep.p99_ms:.3f} ms (smoke reading); recall@{rep.k} vs the "
          f"full-index oracle {rep.recall_at_k!r}; index {rep.index}")
    check(n_batches >= 3, "serve: at least 3 query batches answered")
    check(rep.recall_at_k is not None and np.isfinite(rep.recall_at_k)
          and 0.0 < rep.recall_at_k <= 1.0,
          "serve: recall against the oracle is in (0, 1]")
    check(rep.top_urls.shape == (rep.n_queries, rep.k)
          and bool(np.isfinite(rep.top_scores).all()),
          "serve: answers have the expected shape and finite scores")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: deployment A + ref + serving on one chip; "
                         "4: the four-chip exchange path and its ref run")
    args = ap.parse_args(argv)

    cache = use_compile_cache()
    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, "
              f"{len(jax.devices())} visible", file=sys.stderr)
        return 2

    from repro.configs.base import CrawlConfig, scaled
    from repro.kernels import registry
    from repro.launch.mesh import make_host_mesh

    devices = jax.devices()[:args.chips]
    mesh = make_host_mesh(devices=devices)
    kind = devices[0].device_kind
    print(f"device: {devices[0].platform} / {kind} x {len(devices)} "
          f"(of {len(jax.devices())} visible); compile cache {cache}")

    impls = {f: registry.resolve_impl(f, "auto") for f in FAMILIES}
    print("kernel_impl=auto resolves to: "
          + ", ".join(f"{f}={i}" for f, i in impls.items()))
    check(all(impls[f] == "pallas" for f in COMPILED)
          and all(impls[f] == "ref" for f in FAMILIES if f not in COMPILED),
          "select families run compiled Pallas; bloom, dedup_deposit and "
          "opic_update resolve to ref")

    full = CrawlConfig(n_domains=DOMAINS_PER_CHIP * args.chips,
                       ordering="opic_url", coordination="exchange",
                       fused_dispatch=True, kernel_impl="auto")
    print(f"deployment A x {args.chips}: {full.n_domains} domains, "
          f"{full.n_slots} frontier rows ({full.n_slots // args.chips} per "
          f"chip), queues {full.frontier_capacity}, fetch_batch "
          f"{full.fetch_batch}, outlinks {full.outlinks_per_page}, "
          f"{full.bloom_hashes} Bloom hashes, dispatch every "
          f"{full.dispatch_interval} steps, dispatch_capacity "
          f"{full.dispatch_capacity}, {full.ordering}, {full.coordination}")
    limit = devices[0].memory_stats()["bytes_limit"]
    # the one-chip run searches down from the default; four chips hold the
    # same per-chip shapes, so they check the cut the one-chip run found
    start = full.bloom_bits_log2 if args.chips == 1 else \
        full.bloom_bits_log2 - 1
    bits, need = bloom_cut(full, mesh, limit, start)
    cfg = scaled(full, bloom_bits_log2=bits)
    print(f"cut: bloom_bits_log2 {full.bloom_bits_log2} -> {bits} (reason: "
          f"the byte-per-bit filter holds {full.n_slots // args.chips} rows "
          f"x 2^{full.bloom_bits_log2} B per chip, and the fused chunk "
          f"keeps its input and output state at once; at 2^{bits} it needs "
          f"{need} B per device of {limit} B)")

    label = "A" if args.chips == 1 else "Ax4"
    got = crawl(cfg, mesh, devices, label)
    check_crawl(got, label)
    if args.chips == 4:
        check(got["stats"]["dispatch_sent"] > 0,
              f"{label}: URLs were shipped through the exchange")
        shards = got["sess"].state.bloom_bits.addressable_shards
        rows = cfg.n_slots // args.chips
        check(sorted(s.device.id for s in shards)
              == sorted(d.id for d in devices)
              and all(s.data.shape == (rows, 1 << bits) for s in shards),
              f"{label}: each device holds its own ({rows}, 2^{bits}) "
              f"Bloom shard")
    memory_line(devices)
    del got["sess"]
    gc.collect()

    ref = crawl(scaled(cfg, kernel_impl="ref"), mesh, devices, label + "/ref")
    del ref["sess"]
    gc.collect()
    compare_ref(got, ref, label)

    if args.chips == 1:
        serve(cfg, mesh)
        memory_line(devices)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
