#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python bench/run.py --workload garnet_1m.solve --seed 7 --seconds 45 --trace 0

From the root of a madupite checkout on a machine that holds the chips the
cell asks for (``BENCHMARK.json``).  With ``--trace 0`` the result carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
read from a profiler trace.  The last stdout line is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, last,
``checks``: each number compared with the reference beside its limit; the
same numbers are the last stderr lines).  Without the accelerator, or with
another number of chips, it exits non-zero and prints no result.
"""

import time

T_START = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
