"""The benchmark's own CPU tests.  JAX is held to the CPU before anything
imports it, and the program is importable beside the ``bench`` package.

``bench`` and ``bench/tests`` are packages so that this file is
``bench.tests.conftest``: a second top-level ``conftest`` would shadow the
repository's own ``tests/conftest.py`` for tests that import it by name."""
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.append(str(p))
