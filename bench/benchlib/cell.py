"""One run of one cell: set up, measure the window, read the metrics, check
the outputs. ``run.py`` calls ``run_cell`` after it has found the chip;
the tests call it on the CPU at toy sizes, and ``calibrate.py`` calls it
once per seed in one process."""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

import jax
import numpy as np

from benchlib import (check, drive, endtoend, rooflines, spec, tracing,
                      traffic)
from benchlib.deploy import Deployment


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def setup_cache() -> None:
    """JAX's persistent compilation cache where the program puts it, with
    small programs kept too, so that only a checkout's first run
    compiles."""
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def max_pack(m: dict, tp: dict) -> int:
    """Most prompts one packed prefill call can hold under this traffic."""
    dep = m["deployment"]
    cap = max(dep["prefill_buckets"])
    shortest = min(c["prompt"]["min"] for c in traffic.classes(tp))
    return max(1, min(dep["n_slots"], cap // shortest))


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             start: float, peaks: Optional[dict] = None,
             bench: Path = spec.BENCH_DIR, root: Path = spec.ROOT,
             control: bool = False, fault=None) -> dict:
    """The result line's object for one run (``correct`` and all). With
    ``control`` the check also reads the fp8 control's widest gap on the
    same sample (``checks.control_gap``); it does not change ``correct``.
    ``fault(dep)`` plants a fault in the deployment before the window."""
    m = spec.config(cell["config"], bench)
    tp = spec.traffic(cell["traffic"], bench)
    dep = Deployment(m, seed, bench)
    t = traffic.generate(tp, seed, dep.expert_names(), m["vocab_size"],
                         seconds)
    log(f"setup: {len(dep.hosts)} experts x {dep.expert_bytes} bytes built "
        f"in {dep.build_s:.3f}s")
    warm_s = dep.warm(t.popularity, max_pack(m, tp))
    log(f"setup: warm-up and HBM tier fill took {warm_s:.3f}s; tier holds "
        f"{dep.coe.cache.expert_ids()}")
    if fault is not None:
        fault(dep)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    # what set-up left alive stays out of the collector's full passes, as
    # a long-running server's would
    gc.collect()
    gc.freeze()
    try:
        rec = drive.run_window(dep, t, seconds, trace_dir)
        drain_end = time.perf_counter()
        red = None
        if trace:
            red = tracing.reduce(tracing.extract(
                tracing.xplane_path(trace_dir)))
    finally:
        gc.unfreeze()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    peak = peak_bytes()
    ctx = {"start": start, "drain_end": drain_end, "peak_bytes": peak,
           "m": m, "peaks": peaks, "trace": red, "cell": cell["name"],
           "bench": bench,
           "kernels": rooflines.names(red, m, bench) if red else {}}
    report(rec, ctx)

    metrics = {}
    kind = "per_layer" if trace else "end_to_end"
    for x in spec.metrics_of(cell["name"], kind, root):
        if trace:
            v = spec.metric_reader(x["name"], bench)(rec, ctx)
        else:
            v = endtoend.METRICS[x["name"]](rec, ctx)
        if v is not None:
            metrics[x["name"]] = {"value": v, "unit": x["unit"]}

    dev = dict(device_info(), memory_peak_bytes=peak)
    out = {"attempted": len(rec.requests),
           "failed": sum(not r["finished"] for r in rec.requests)}
    if red is not None:
        dev.update(busy_s=red["busy_s"], window_s=red["window_s"])

    # the output check, once the program's state is freed
    in_window = {r["rid"] for r in rec.requests}
    done = [r for r in rec.done if r.rid in in_window]
    # retained sessions and indexed prefixes hold blocks by design: drop
    # them, and every block still held has leaked
    dep.engine.release_shared()
    pool_ok = dep.engine.pool.stats.blocks_in_use == 0
    dep.close()
    t_check = time.perf_counter()
    ref = spec.reference(m, bench)
    reqs = check.sample(done, seed, tp["sample_tokens"])
    gaps = check.widest_gaps(m, ref, dep.hosts, reqs)
    short = sum(len(r.output) != r.max_new_tokens for r in done)
    checks = {
        "gap": {"value": max(gaps.values(), default=1e9),
                "limit": m["check"]["gap_limit"]},
        "unfinished": {"value": out["failed"], "limit": 0},
        "short_outputs": {"value": short, "limit": 0},
        "leaked_kv_blocks": {"value": 0 if pool_ok else 1, "limit": 0},
    }
    if control:
        cg = check.widest_gaps(m, ref, dep.hosts, reqs, control=True)
        checks["control_gap"] = {"value": max(cg.values()),
                                 "limit": m["check"]["gap_limit"]}
    log(f"check: {len(reqs)} requests, "
        f"{sum(len(r.output) for r in reqs)} served tokens against the "
        f"reference in {time.perf_counter() - t_check:.3f}s")
    correct = all(c["value"] <= c["limit"] for k, c in checks.items()
                  if k != "control_gap")
    for k, c in checks.items():
        log(f"{k} {c['value']!r} limit {c['limit']!r}")
    result = {"correct": correct, **out, "metrics": metrics, "device": dev}
    if red is not None:
        result["breakdown"] = tracing.breakdown(red, ctx["kernels"])
    result["checks"] = checks
    return result


def report(rec, ctx) -> None:
    """Earlier stderr lines: what the run saw, for the reader of a log."""
    late = np.asarray(rec.lateness_s or [0.0])
    c = rec.counters
    log(f"window: {rec.window_s}s, {len(rec.requests)} requests arrived, "
        f"{sum(r['finished'] for r in rec.requests)} finished, "
        f"{rec.tokens_in_window} tokens in window; generator lateness p95 "
        f"{np.percentile(late, 95):.6f}s max {late.max():.6f}s; compiles in "
        f"window {rec.compiles_in_window}; trace pause {rec.pause_s:.3f}s")
    log("counters: " + " ".join(f"{k}={v:.6g}" for k, v in c.items()))
    never = ctx["drain_end"]
    log("requests ttft_s: " + " ".join(
        f"{endtoend.ttft(r, never):.4f}" for r in rec.requests))
    log("requests tpot_ms: " + " ".join(
        f"{x:.3f}" for x in endtoend.tpots_ms(rec.requests)))
    if ctx["trace"] is not None:
        t = ctx["trace"]
        log(f"trace: window {t['window_s']:.6f}s busy {t['busy_s']:.6f}s "
            f"gaps {t['n_gaps']}; {len(rec.rounds)} decode rounds and "
            f"{len(rec.prefills)} prefill calls traced")
        for k in rooflines.kernels(ctx["m"], ctx["bench"]):
            labs = [x for x, n in ctx["kernels"].items() if n == k]
            log(f"kernel {k}: {sum(t['ops'][x] for x in labs)!r}s in "
                f"{sum(t['calls'][x] for x in labs)!r} calls, "
                f"{rooflines.share(rec, ctx, k)!r}% of its roofline")
