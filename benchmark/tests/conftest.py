"""Rehearsals of the benchmark (``python -m pytest benchmark/tests``): not
collected by the repository's tier-1 run, which walks ``tests/``. They run on
the CPU and print no device metric."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
