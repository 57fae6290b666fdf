#!/usr/bin/env python3
"""FedGS chip benchmark: one run of one cell of ``BENCHMARK.json``.

  python3 bench/run.py --workload paper.static --seed 7 --seconds 30 --trace 0

Run from the root of a checkout on a machine with the TPU chips the cell
asks for. Set-up (build, compile or cache load, the first rounds) is timed
as ``setup_s``; then whole rounds run until ``--seconds`` have passed.
With ``--trace 1`` two rounds are profiled instead and the per-layer
metrics read from the trace. Every run then checks the rounds the program
ran in set-up against the plain reference, prints each compared number
beside its limit as the last lines on stderr, and prints one JSON result
line last on stdout. Without the chips it exits non-zero and prints no
result.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from fedbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
