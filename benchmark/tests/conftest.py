"""benchmark/tests: CPU tests of the benchmark's own machinery.
Run with ``python -m pytest benchmark/tests`` from the checkout's root."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
