"""Run one benchmark cell once and print its result as the last line.

    python3 perfbench/run.py --workload A.crawl --seed 7 --seconds 20 --trace 0

The cells, their configurations, traffic mixes and metrics are named in
BENCHMARK.json at the root of the checkout; see perfbench/harness.py.
"""
import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime's logs stay inside the checkout
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "perfbench_out",
                                                  "tpu_logs"))

from perfbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_PROCESS))
