"""Entry point of the benchmark: see harness.py and README.md."""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    from benchmark import harness

    sys.exit(harness.main(sys.argv[1:], ROOT, T_START))
