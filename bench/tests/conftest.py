"""Tests of the benchmark itself, run on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

They sit outside the repository's ``testpaths``."""
from __future__ import annotations

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
