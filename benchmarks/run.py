"""Benchmark harness — one module per paper claim (the paper has no numeric
tables, so its §III/§IV claims C1..C5 are the "tables"), plus the roofline
report from the dry-run artifacts.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run overlap    # one suite

A suite whose ``main`` returns a dict gets that dict persisted as
``BENCH_<suite>.json`` next to this file's repo root — the mechanism behind
the committed perf trajectories (the ``BENCH_*.json`` files).
"""
from __future__ import annotations

import json
import pathlib
import sys
import time
import traceback

SUITES = ("overlap", "dispatch", "serve", "kernel_dispatch", "ordering",
          "session_scan", "scaling", "fault", "rebalance", "obs_overhead",
          "roofline")

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    args = (argv if argv is not None else sys.argv[1:]) or list(SUITES)
    failures = []
    for name in args:
        t0 = time.time()
        print(f"\n{'='*74}\nbenchmark suite: {name}\n{'='*74}")
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            result = mod.main()
            if isinstance(result, dict):
                # a suite may pin its artifact name (obs_overhead -> obs)
                out = ROOT / f"BENCH_{getattr(mod, 'BENCH_NAME', name)}.json"
                out.write_text(json.dumps(result, indent=2, sort_keys=True)
                               + "\n")
                print(f"-- wrote {out}")
            print(f"-- {name} done in {time.time()-t0:.1f}s")
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        print(f"\nFAILED suites: {failures}")
        raise SystemExit(1)
    print("\nall benchmark suites completed")


if __name__ == "__main__":
    main()
