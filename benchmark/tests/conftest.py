"""The benchmark's own tests: run with ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests``.  They are not part of the repository's tier-1 suite."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
