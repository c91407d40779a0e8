"""CPU tests of the benchmark: ``python -m pytest bench/tests``.

They run on the CPU (JAX is pinned to it here), at tiny sizes, except the
compiles for a described TPU v5e chip in ``test_cells.py``.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (os.path.join(ROOT, "src"), BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
