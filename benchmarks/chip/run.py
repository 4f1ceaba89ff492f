#!/usr/bin/env python3
"""On-chip benchmark of the straggler-mitigation system: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout on a machine with a TPU; without one it
exits non-zero and prints no result.  The cells, their configurations,
traffic, entries and per-layer metrics are named in ``BENCHMARK.json`` and
found as files under this directory (see ``yardstick/harness.py``).  The
last line of standard output is the result as one JSON object.
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from yardstick.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
