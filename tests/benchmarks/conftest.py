"""Puts the repo root on sys.path, so `import benchmarks` finds the
benchmark's package (this directory has the same last name and no
`__init__.py`; the package at the root wins)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
