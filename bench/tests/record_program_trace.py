#!/usr/bin/env python3
"""Traced runs of one cell read by the program's own spans and scopes.

    python3 bench/tests/record_program_trace.py --workload <name> \
        --seeds 11,12 --seconds 51 [--out-dir <dir>] \
        [--record bench/tests/data/<name> --keep-ms 300]

Runs the cell once per seed in one process, each exactly as
``run.py --trace 1`` does, and also reduces the same profile with
``benchlib.programs``: idle seconds by program span, spans counted,
device seconds by program and scope, and the readings
``decode_host_gap_ms``, ``sched_host_gap_ms`` and
``decode_scan_copy_share``. Prints one JSON line per seed with those and
the cross-checks: idle seconds inside ``engine.step`` against those inside
the harness's ``bench.step``, each fused kernel's seconds under its scope
against its seconds by shape, and the device seconds no program owns.
``--out-dir`` keeps each run's full reduction and the run's result line.

``--record`` keeps ``--keep-ms`` milliseconds from the middle of the first
run's traced window as ``<record>.program.json.gz`` and its reduction as
``<record>.program-counts.json``: check the counts by hand against the
events before committing them; ``test_bench_programs.py`` holds the
reduction to them.
"""
from __future__ import annotations

import time

START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

from record_trace import WIDTHS  # noqa: E402


def idle_inside(ev: dict, spans: list) -> float:
    """Idle seconds of the chips that fall inside any of ``spans``."""
    from benchlib import programs
    _, w0, wd = next(h for h in ev["host"] if h[0] == "bench.traced")
    union = [("x", a, b - a) for a, b in
             programs.tracing._union([(s, s + d) for _, s, d, *_ in spans])]
    pieces = [p for p in programs._timeline(union, w0, w0 + wd) if p[2]]
    total = 0
    for ops in ev["devices"].values():
        gaps = [(a, b, None) for a, b in programs._gaps(ops, w0, w0 + wd)]
        total += sum(b - a for a, b, _, _ in
                     programs._intersect(pieces, gaps))
    return total / len(ev["devices"]) / 1e9


def checks(ev: dict, red: dict, pr: dict, m: dict) -> dict:
    from benchlib import programs, rooflines
    eng = programs.engine_thread(ev["program_host"])
    bench_step = idle_inside(ev, [h for h in ev["host"]
                                  if h[0] == "bench.step"])
    engine_step = idle_inside(ev, [h for h in ev["program_host"]
                                   if h[0] == "engine.step" and h[3] == eng])
    names = rooflines.names(red, m)
    where = {}          # label -> the (program, scope) pairs it ran under
    for dev, ops in ev["devices"].items():
        for (lab, _, _), meta in zip(ops, ev["op_meta"][dev]):
            where.setdefault(lab, set()).add(tuple(meta))
    kernels = {}
    for k, scope in programs.KERNEL_SCOPES.items():
        labs = [x for x, n in names.items() if n == k]
        kernels[k] = {"seconds": sum(red["ops"][x] for x in labs),
                      "under_scope_s": sum(
                          red["ops"][x] for x in labs
                          if where[x] == {(programs.STEP, scope)})}
    top = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:12]
    return {"idle_in_bench_step_s": bench_step,
            "idle_in_engine_step_s": engine_step,
            "engine_step_self_share": (
                pr["step_children"].get("engine.step", 0.0) / engine_step
                if engine_step else None),
            "kernels": kernels,
            "unattributed_share": (pr["scope_ops"].get(programs.NONE, 0.0)
                                   / red["busy_s"]),
            "top_ops": [[lab, v, sorted(where.get(lab, ()))]
                        for lab, v in top]}


def reading(ev: Optional[dict], m: dict) -> dict:
    """The readings of one run's events, with the reduction and checks
    they come from; only ``readings`` (all None) without a reduction."""
    from benchlib import programs, tracing
    red = tracing.reduce(ev) if ev else None
    pr = programs.reduce(ev) if red else None
    out = {"readings": programs.summary(pr, red)}
    if pr is not None:
        out.update(pr)
        out.update(busy_s=red["busy_s"], checks=checks(ev, red, pr, m))
    return out


def record(ev: dict, m: dict, out: Path, keep_ms: int) -> None:
    """``keep_ms`` from the middle of the traced window, and its counts."""
    from benchlib import programs, tracing
    _, w0, wd = next(h for h in ev["host"] if h[0] == "bench.traced")
    a = w0 + (wd - keep_ms * 1_000_000) // 2
    cut = programs.trim(ev, a, a + keep_ms * 1_000_000)
    red, pr = tracing.reduce(cut), programs.reduce(cut)
    counts = {"window_s": red["window_s"], "busy_s": red["busy_s"],
              **{k: pr[k] for k in ("program_counts", "program_gaps",
                                    "step_children", "idle_by_program",
                                    "span_program_gaps", "scope_ops")},
              "readings": programs.summary(pr, red),
              "model": {k: m[k] for k in WIDTHS}}
    out.with_name(out.name + ".program.json.gz").write_bytes(
        gzip.compress(json.dumps(cut).encode()))
    out.with_name(out.name + ".program-counts.json").write_text(
        json.dumps(counts, indent=1) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out-dir", default=None)
    ap.add_argument("--record", default=None)
    ap.add_argument("--keep-ms", type=int, default=300)
    args = ap.parse_args(argv)

    from benchlib import cell as cellmod, programs, spec, tracing
    dev = cellmod.device_info()
    if dev["platform"] != "tpu":
        print("no TPU found", file=sys.stderr)
        return 2
    cellmod.setup_cache()
    cell = spec.workload(args.workload)
    m = spec.config(cell["config"])
    peaks = spec.peaks(dev["kind"])
    out_dir = Path(args.out_dir) if args.out_dir else None
    if out_dir:
        out_dir.mkdir(parents=True, exist_ok=True)
    seen = {}
    xplane_path, deployment = tracing.xplane_path, cellmod.Deployment

    class Kept(deployment):
        """The run's deployment, kept where ``capture`` finds it."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            seen["dep"] = self

    def capture(trace_dir):
        """The run finds its profile here: read the program's spans and
        scopes from it too, before the run removes it. The decode step's
        operations find their scopes in its compiled HLO."""
        path = xplane_path(trace_dir)
        step = seen["dep"].engine.runner.compiled_extend()
        hlo = {programs.STEP: step.as_text()} if step is not None else {}
        try:
            seen["ev"] = programs.extract(path, hlo)
        except ValueError as e:
            print(f"program attribution failed: {e}", file=sys.stderr)
            seen["ev"] = None
        return path

    tracing.xplane_path, cellmod.Deployment = capture, Kept
    try:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            t = START if i == 0 else time.perf_counter()
            res = cellmod.run_cell(cell, seed, args.seconds, True, t,
                                   peaks=peaks)
            ev = seen.pop("ev")
            line = {"seed": seed, "correct": res["correct"],
                    "metrics": {k: v["value"]
                                for k, v in res["metrics"].items()},
                    **reading(ev, m)}
            print(json.dumps(line), flush=True)
            if out_dir:
                (out_dir / f"{args.workload}-{seed}.json").write_text(
                    json.dumps({"result": res, **line}, indent=1))
            if args.record and i == 0 and line["readings"]:
                record(ev, m, Path(args.record), args.keep_ms)
    finally:
        tracing.xplane_path, cellmod.Deployment = xplane_path, deployment
    return 0


if __name__ == "__main__":
    sys.exit(main())
