"""The benchmark's tests: they run on the CPU and load no TPU library.

``bench/`` (the harness package ``fedbench`` and the references) and the
program's ``src/`` go on the path here, as ``bench/run.py`` puts them.
"""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH.parent / "src", BENCH):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
