"""The harness's own tests: ``python -m pytest benchmark/tests`` from the
checkout's root, on the CPU. They are not part of the repo's tier-1 run."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
