"""The benchmark's own tests, run by hand on the CPU:

  JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The repository's tier-1 suite (``testpaths = tests``) does not collect
them."""
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
