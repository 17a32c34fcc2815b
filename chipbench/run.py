#!/usr/bin/env python3
"""Benchmark entry point: one run of one cell of BENCHMARK.json.

    python3 chipbench/run.py --workload qwen2.5-3b.swde --seed 7 \
        --seconds 51 --trace 0

Prints progress, the number of compilations inside the measured window and
each number `correct` compares beside its limit on standard error, and one
JSON result object as the last line of standard output (`harness.py` says
what it holds). Refuses to run without a TPU, or with fewer chips than the
cell asks for.
"""
import time

T_START = time.perf_counter()   # set-up is measured from here

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
