#!/usr/bin/env python3
"""Runs one cell of ``BENCHMARK.json`` once, on the chip it is started on.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time: find the accelerator (no CPU path: the number
of TPU devices must equal the cell's ``chips``), build, warm up, measure,
check, print. The last line of stdout is the result as one JSON object.
"""

import time

T_PROCESS = time.monotonic()

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import runner  # noqa: E402

if __name__ == "__main__":
    sys.exit(runner.main(sys.argv[1:], t_process=T_PROCESS))
