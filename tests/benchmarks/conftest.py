"""The benchmark's own tests. `benchmarks` is the package at the root of the
checkout; a test run started elsewhere still has to find it."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
