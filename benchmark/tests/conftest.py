"""The benchmark's own tests run on the CPU (no libtpu at import):
    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
They are run by hand and in the CPU rehearsal, not by the repo's tier-1."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
