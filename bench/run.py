#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a ``workloads`` name of ``BENCHMARK.json``.  The run builds
the cell's inputs from ``--seed``, warms the cell's programs (set-up),
calls the cell's entry back to back for ``--seconds``, checks a sample of
the results against the plain reference, and prints one JSON object as the
last line of standard output.  With no TPU, or fewer chips than the cell
needs, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness.runner import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:], T_START))
