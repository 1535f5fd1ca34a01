"""The benchmark's own tests: on the CPU, at small sizes.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

They sit outside the repository's ``testpaths``; run them by hand.
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
