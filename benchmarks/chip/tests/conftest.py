"""Puts the benchmark's modules and the program's sources on the path.

Run from the root of the checkout:

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (os.path.join(ROOT, "src"), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
