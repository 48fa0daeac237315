#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and per-layer metrics are found
by name from ``BENCHMARK.json`` at the root of the checkout (see
``bench/harness.py``). The last line of standard output is one JSON object;
with no TPU, or fewer chips than the cell asks for, the run exits non-zero
and prints no such line.
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

if __name__ == "__main__":
    from bench.harness import main
    sys.exit(main(t_start=T_START))
