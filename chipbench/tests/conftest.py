"""The benchmark's own tests run on the CPU, in seconds: ``python -m pytest
chipbench/tests -q``.  The shipped command has no CPU fallback; these tests
stub the device check on their side."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
