#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

See ``bench/harness.py`` for what a run does and ``PERF.md`` for the cells.
"""

import time

T_START = time.monotonic()  # set-up counts from here: imports and all

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.harness import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run(t_start=T_START))
