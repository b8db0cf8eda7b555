"""The benchmark's tests run on the CPU at small scales.

``python -m pytest bench/tests -q`` from the root of the checkout.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
