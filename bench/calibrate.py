#!/usr/bin/env python3
"""Readings that the output check's limit is set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seconds <s> \
        --seeds 11,12,13 [--control]

Runs the cell once per seed in one process, exactly as ``run.py`` does,
and prints one JSON line per seed: the widest gap of the served tokens
(the lower reading of the limit is the largest over a dozen seeds or more)
and, with ``--control``, the widest gap of the fp8-weight control at the
same positions (the upper reading is the smallest over three seeds or
more). The benchmark's own runs never compute the control.
"""
from __future__ import annotations

import time

START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    from benchlib import cell as cellmod, spec
    dev = cellmod.device_info()
    if dev["platform"] != "tpu":
        print("no TPU found", file=sys.stderr)
        return 2
    cellmod.setup_cache()
    cell = spec.workload(args.workload)
    peaks = spec.peaks(dev["kind"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        res = cellmod.run_cell(cell, seed, args.seconds, False, t,
                               peaks=peaks, control=args.control)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "checks": res["checks"],
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
