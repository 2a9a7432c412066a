"""Find the search cell's knee: the highest offered rate the system serves
without its backlog growing, by a sweep of fixed rates on the chip.

    python3 perfbench/sweep.py --workload A.serve --rates 200 400 600 \\
        --seconds 20 --seed 5

For each rate it runs the cell once (no warm backlog) and prints the
dispatch intervals' wall times, the queries each interval answered, the
p95 latency and the answered rate. While the rate is below the knee the
intervals settle; above it each interval answers more than the last and
they keep stretching. The cell's rate is set once, from this sweep, at
about four fifths of the knee; this is not part of a benchmark run.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime's logs stay inside the checkout
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "perfbench_out",
                                                  "tpu_logs"))

import numpy as np  # noqa: E402

from perfbench import harness as H  # noqa: E402
from perfbench import spec as SP  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    base = SP.load_cell(args.workload)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    devices, why = H.accelerator(base.chips)
    if devices is None:
        H.log(f"sweep: {why}")
        return 2
    for rate in args.rates:
        traffic = copy.deepcopy(base.traffic)
        traffic["search"].update(rate_qps=rate, warm_backlog_s=0.0)
        cell = SP.Cell(base.name, base.chips, base.config, traffic,
                       base.end_to_end, base.per_layer)
        out = H.run_cell(cell, seed=args.seed, seconds=args.seconds,
                         trace=False, devices=devices,
                         t_process=time.perf_counter())
        rec = out["rec"]
        lat = rec.latency_ms
        print(json.dumps({
            "rate": rate, "calls": rec.calls,
            "call_s": [round(c, 4) for c in rec.call_s],
            "call_queries": rec.call_queries,
            "p95_ms": float(np.percentile(lat, 95)) if len(lat) else None,
            "answered_per_s": rec.queries_answered / rec.window_s,
            "pages_per_s": rec.pages / rec.window_s,
            "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
