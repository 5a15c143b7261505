"""``JAX_PLATFORMS=cpu python -m pytest benchmark/tests`` — the
benchmark's own tests, on the CPU.  Not collected by the repo's tier-1
run, which names ``tests/`` only."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
