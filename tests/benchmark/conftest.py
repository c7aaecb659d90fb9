"""The benchmark's tests import its modules as the harness does: with
`benchmarks/` at the front of the path (`lib`, `readers`)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
