#!/usr/bin/env python3
"""Serve one cell of ``BENCHMARK.json`` on the chip and print its result.

    python3 chipbench/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout. The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and ``checks`` last); the
last lines of standard error give each compared number beside its limit.
It exits with an error, and prints no result, without a TPU.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
HERE = str(Path(__file__).resolve().parent)

if __name__ == "__main__":
    # the benchmark's modules are imported as the ``chipbench`` package
    sys.path[:] = [p for p in sys.path if p != HERE]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench.harness import main
    sys.exit(main(sys.argv[1:], t_start=T_START, root=ROOT))
