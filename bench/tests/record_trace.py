#!/usr/bin/env python3
"""Record a small device trace of one cell for the trace-reduction tests.

    python3 bench/tests/record_trace.py --workload <name> --seed <n> \
        --seconds 20 --out bench/tests/data/<name>

Runs the cell's window with the profiler on, as ``run.py --trace 1`` does,
keeps ``--keep-ms`` milliseconds from the middle of the traced window, and
writes ``<out>.events.json.gz`` (the extracted events) and
``<out>.counts.json`` (the reduction's window, busy time, gaps, seconds per
kernel and the model's widths). Check the counts by hand against the events
before committing them: ``test_bench_trace.py`` holds the reduction to
them.
"""
from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

WIDTHS = ("model_type", "hidden_size", "num_hidden_layers",
          "num_attention_heads", "num_key_value_heads", "head_dim",
          "intermediate_size", "vocab_size", "deployment")


def trim(ev: dict, keep_ns: int) -> dict:
    """The events that overlap ``keep_ns`` from the middle of the traced
    window, with the window cut to that span."""
    _, w0, wd = next(h for h in ev["host"] if h[0] == "bench.traced")
    a = w0 + (wd - keep_ns) // 2
    b = a + keep_ns
    over = lambda x: x[1] < b and x[1] + x[2] > a
    return {"devices": {d: [x for x in ops if over(x)]
                        for d, ops in ev["devices"].items()},
            "host": [["bench.traced", a, keep_ns]]
            + [x for x in ev["host"] if x[0] != "bench.traced" and over(x)]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--keep-ms", type=int, default=120)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from benchlib import cell as cellmod, drive, rooflines, spec, tracing
    from benchlib import traffic
    from benchlib.deploy import Deployment
    if cellmod.device_info()["platform"] != "tpu":
        print("no TPU found", file=sys.stderr)
        return 2
    cellmod.setup_cache()
    cell = spec.workload(args.workload)
    m, tp = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    dep = Deployment(m, args.seed)
    t = traffic.generate(tp, args.seed, dep.expert_names(), m["vocab_size"],
                         args.seconds)
    dep.warm(t.popularity, cellmod.max_pack(m, tp))
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        drive.run_window(dep, t, args.seconds, trace_dir)
        ev = trim(tracing.extract(tracing.xplane_path(trace_dir)),
                  args.keep_ms * 1_000_000)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    dep.close()
    red = tracing.reduce(ev)
    names = rooflines.names(red, m)
    counts = {
        "window_s": red["window_s"], "busy_s": red["busy_s"],
        "n_gaps": red["n_gaps"],
        "kernel_s": {k: tracing.kernel_seconds(
            red, lambda x, k=k: names.get(x) == k)
            for k in sorted(set(names.values()))},
        "gaps_s": red["gaps"],
        "model": {k: m[k] for k in WIDTHS}}
    out = Path(args.out)
    out.with_name(out.name + ".events.json.gz").write_bytes(
        gzip.compress(json.dumps(ev).encode()))
    out.with_name(out.name + ".counts.json").write_text(
        json.dumps(counts, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
