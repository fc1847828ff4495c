#!/usr/bin/env python3
"""On-chip serving benchmark: one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell's seeded model on the chip, serves its closed-loop
traffic through the program's paged ``Engine`` for ``--seconds``, and
prints one JSON object as the last line of standard output: with
``--trace 0`` the end-to-end metrics, with ``--trace 1`` the per-layer
metrics read from the engine's hooks and a profiler trace.  Exits
non-zero, printing no result, without a TPU.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    sys.exit(harness.main())
