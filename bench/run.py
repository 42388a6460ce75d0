#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, metrics and bounds are in ``BENCHMARK.json``; ``harness.py`` says
what one run does. Exits non-zero, printing no result, where JAX finds no
TPU, a TPU without an entry in ``peaks.json``, or fewer chips than the cell
asks for.
"""

import time

T_PROCESS = time.perf_counter()  # setup_s counts from here

import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
# the TPU runtime writes its logs to /tmp/tpu_logs unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_process=T_PROCESS))
