"""chipbench's own tests: run by path (``python -m pytest chipbench/tests``),
on the CPU, not part of the repository's tier-1 suite."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
