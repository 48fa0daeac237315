#!/usr/bin/env python3
"""Readings from which a cell's limits are set; not part of a benchmark run.

    python bench/calibrate.py --workload <cell> --seeds 1 2 3 ...

For each seed, in one process at the cell's own size: the program's
numbers against the plain reference (sound runs), and the numbers of the
control and of the faults put in the program's place. One JSON object per
seed on standard output.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = harness.load_cell(args.workload)
    harness.enable_cache()
    harness.require_devices(spec.chips)
    driver = harness.driver_of(spec)
    for row in driver.calibrate(spec, harness.reference_of(spec.config),
                                args.seeds):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
