#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip it is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` and its configuration, traffic mix and
metric readers under ``bench/`` by name; builds the deployment from the
seed, warms every shape the window uses, measures ``--seconds`` seconds,
and checks the served tokens against the plain reference. The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``, and
last ``checks``: each number compared beside its limit. The last lines of
standard error repeat the checks.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result. JAX's persistent compilation cache is kept where the
program's ``setup_compile_cache()`` puts it: ``JAX_COMPILATION_CACHE_DIR``
when that is set, else ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import time

START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    from benchlib import cell as cellmod, spec
    cell = spec.workload(args.workload)
    cellmod.setup_cache()
    dev = cellmod.device_info()
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        print("no TPU found: the benchmark runs only on the chip",
              file=sys.stderr)
        return 2
    if dev["count"] < cell["chips"]:
        print(f"cell {cell['name']} needs {cell['chips']} chips, found "
              f"{dev['count']}", file=sys.stderr)
        return 2
    peaks = spec.peaks(dev["kind"])
    result = cellmod.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), START, peaks=peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
