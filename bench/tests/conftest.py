"""The benchmark's own tests run on the CPU: the bench directory and the
program's sources go on the path, as ``bench/run.py`` puts them."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
