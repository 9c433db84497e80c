"""Run by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``.
Not part of the repo's tier-1 suite (``tests/``)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
