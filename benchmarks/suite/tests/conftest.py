"""Puts the checkout and the program's sources on ``sys.path``.

These tests are run by hand from the checkout's root (the repository's own
test run collects ``tests/`` only):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/suite/tests -q
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
