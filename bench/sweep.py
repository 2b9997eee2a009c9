#!/usr/bin/env python3
"""Find the highest arrival rate an open-loop cell sustains, on the chip.

    python3 bench/sweep.py --workload <name> --seeds 1,2,3 --seconds <s> \
        --rates 2,4,6,8

Builds the cell's deployment once, then runs one window per rate and seed,
lowest rate first, each drained before the next and each with the HBM tier
filled for its seed. For each it prints one JSON line: requests arrived and
finished, the queue left when the window closed, how long the drain took,
TTFT and TPOT percentiles, and output tokens per second. Past the knee the queue at the close grows with the rate and the
drain lengthens. The benchmark's runs do not use this; its result is a
number written into the traffic file by hand (``rate_per_s``).
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
    ap.add_argument("--seeds", default="0",
                    help="comma-separated traffic seeds, one window each")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated arrival rates, requests/s")
    args = ap.parse_args(argv)

    from benchlib import cell as cellmod, drive, spec, traffic
    from benchlib.deploy import Deployment
    from benchlib.endtoend import pct, ttft, tpot
    dev = cellmod.device_info()
    print(f"device: {dev}", flush=True)
    if dev["platform"] != "tpu":
        print("no TPU found", file=sys.stderr)
        return 2
    cellmod.setup_cache()
    cell = spec.workload(args.workload)
    m = spec.config(cell["config"])
    tp = spec.traffic(cell["traffic"])
    seeds = [int(x) for x in args.seeds.split(",")]
    dep = Deployment(m, seeds[0])
    dep.warm(traffic.generate(tp, seeds[0], dep.expert_names(),
                              m["vocab_size"], args.seconds).popularity,
             cellmod.max_pack(m, tp))
    print(f"setup {time.perf_counter() - START:.1f}s", flush=True)
    runs = [(float(r), s) for r in args.rates.split(",") for s in seeds]
    for rate, seed in runs:
        t = traffic.generate(dict(tp, rate_per_s=rate), seed,
                             dep.expert_names(), m["vocab_size"],
                             args.seconds)
        dep.fill_tier(t.popularity)
        rec = drive.run_window(dep, t, args.seconds)
        end = time.perf_counter()
        reqs = rec.requests
        fin = [r for r in reqs if r["finished"]]
        queued = sum(r["admit"] is None or r["admit"] > rec.t_end
                     for r in reqs)
        print(json.dumps({
            "rate": rate, "seed": seed, "arrived": len(reqs),
            "finished": len(fin),
            "finished_in_window": sum(r["done"] <= rec.t_end for r in fin),
            "queued_at_close": queued, "drain_s": end - rec.t_end,
            "ttft_p50_s": pct([ttft(r, end) for r in reqs], 50),
            "ttft_p95_s": pct([ttft(r, end) for r in reqs], 95),
            "tpot_p95_ms": 1e3 * pct([tpot(r) for r in fin
                                      if r["n_out"] > 1], 95),
            "tokens_per_s": rec.tokens_in_window / rec.window_s,
            "switch_s": rec.counters["switch.switch_seconds"],
            "misses": rec.counters["switch.misses"],
            "prefetch_hits": rec.counters["switch.prefetch_hits"],
            "compiles": rec.compiles_in_window}), flush=True)
    dep.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
