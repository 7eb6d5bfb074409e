"""The benchmark's own tests run on the CPU: ``JAX_PLATFORMS=cpu`` is
set before JAX is imported, and the program's sources are importable.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
