"""The benchmark's own library: traffic generation, weights made from the
seed, the drive loop, trace reduction, metric arithmetic and the output
check. What depends on a model's architecture is in ``bench/arch/``. The
program under test (``src/repro``) is imported only in ``deploy.py``,
``drive.py`` and the architecture modules' ``model_config``; the yardstick
(reference, rooflines, peaks, percentiles, trace reduction) never imports
it."""
from __future__ import annotations

from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
