"""Run a cell with a control or a fault planted, and print what it compares.

    python3 perfbench/control.py --workload A.crawl --plant bloom_forget \\
        --seeds 11 12 13 --seconds 10

Every run is expected to come out not correct; each prints its compared
numbers beside their limits. Runs of one call share one process, so the
compiled programs are made once. This is for setting and checking limits;
the benchmark's own runs never plant anything.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime's logs stay inside the checkout
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "perfbench_out",
                                                  "tpu_logs"))

from perfbench import faults as FA  # noqa: E402
from perfbench import harness as H  # noqa: E402
from perfbench import spec as SP  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plant", required=True,
                    choices=sorted({**FA.CONTROLS, **FA.FAULTS, "none": 0}))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = SP.load_cell(args.workload)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    devices, why = H.accelerator(cell.chips)
    if devices is None:
        H.log(f"control: {why}")
        return 2
    plant = {**FA.CONTROLS, **FA.FAULTS}.get(args.plant)
    n_bad = 0
    for seed in args.seeds:
        out = H.run_cell(cell, seed=seed, seconds=args.seconds, trace=False,
                         devices=devices, t_process=time.perf_counter(),
                         tamper=plant)
        checks = out["checks"]
        bad = sorted(n for n in checks if checks[n] > cell.limits[n])
        n_bad += bool(bad)
        print(json.dumps({"workload": args.workload, "plant": args.plant,
                          "seed": seed, "failed": bad, "checks": checks,
                          "pages": out["rec"].pages}), flush=True)
    print(f"{args.plant}: {n_bad} of {len(args.seeds)} runs not correct")
    return 0


if __name__ == "__main__":
    sys.exit(main())
