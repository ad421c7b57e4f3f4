#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.  See bench/README.md.
"""
import time

T_START = time.perf_counter()

import sys                                      # noqa: E402
from pathlib import Path                        # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness                                  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
