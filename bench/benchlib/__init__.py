"""The benchmark's own library: traffic generation, weights made from the
seed, the drive loop, trace reduction, metric arithmetic and the output
check. It imports the program under test (``src/repro``) only in
``deploy.py`` and ``drive.py``; the yardstick (reference, rooflines, peaks,
percentiles, trace reduction) never does."""
from __future__ import annotations

from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
