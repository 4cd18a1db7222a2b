"""Where the benchmark lives, for the tests beside this file."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
DATA_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)
