"""Where the benchmark lives, for its tests (imported by each test file)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
LIB = os.path.join(BENCH, "lib")
if LIB not in sys.path:
    sys.path.insert(0, LIB)
