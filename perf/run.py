"""perf/run.py — one cell of the benchmark, once.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process every time: loads the cell named in `BENCHMARK.json`, makes
its data on the device from the seed, warms the cell's own shapes, measures
for `--seconds`, checks what the timed calls produced against the plain
reference, and prints the result as the last line of standard output. No
TPU, or fewer chips than the cell asks for: non-zero exit and no result.
"""

import sys
import time

T0 = time.perf_counter()  # set-up is counted from here

import os  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perf.lib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(ROOT, T0))
