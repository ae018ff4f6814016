"""The benchmark's own tests run on the CPU, at sizes a test run holds:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They sit outside the repository's ``testpaths``, so they run only when
named."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
