#!/usr/bin/env python3
"""Chip benchmark: run one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` a ``breakdown``), ending in ``checks``: each number that
decided ``correct`` beside its limit. Exits non-zero and prints no result
where JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T0 = time.time()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run(t0=T0))
