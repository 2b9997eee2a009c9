"""The benchmark's tests import its library and the program as ``run.py``
does: ``bench/`` and ``src/`` on the path."""
import sys
from pathlib import Path

_BENCH = Path(__file__).resolve().parents[1]
for p in (str(_BENCH.parent / "src"), str(_BENCH), str(_BENCH / "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)
