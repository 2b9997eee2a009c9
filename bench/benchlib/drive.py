"""The measured window: the benchmark drives ``ServingEngine.submit`` and
``ServingEngine.step`` itself.

Open loop (copied from ``benchmarks/run.py``'s ``serve_trace``: each request
is submitted once its scheduled arrival has passed, then the engine steps):
every request carries its scheduled arrival as ``arrival_s``, so TTFT and
queue wait count any stall of the loop itself; how late the loop submitted
is reported apart. Arrivals stop when the window closes. Closed loop: each
client sends its next request when its previous one finishes. After the
window, requests in flight are drained up to the mix's ``drain_cap_s``; a
request that has not finished by then has failed.

With ``trace`` the run records a profiler trace of a few seconds in the
middle of the window, with ``TraceAnnotation`` spans around the calls the
harness makes and the engine makes on its behalf. Writing the trace out
pauses the loop; the schedule is shifted by the pause, and requests in
flight during it are flagged and left out of the per-request layer metrics.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import numpy as np

from benchlib.traffic import Traffic

COUNTERS = {"serve": ("tokens_out", "decode_rounds", "exec_s", "prefill_s",
                      "switch_s", "occupancy_sum", "admitted", "switches"),
            "switch": ("switch_seconds", "bytes_copied_in", "h2d_seconds",
                       "misses", "hits", "prefetch_hits", "evictions")}


@dataclass
class Record:
    """What one run saw; the layer metrics' readers take their numbers
    from it."""
    window_s: float = 0.0
    t0: float = 0.0
    t_end: float = 0.0
    requests: List[dict] = field(default_factory=list)
    counters: Dict[str, float] = field(default_factory=dict)
    tokens_in_window: int = 0
    lateness_s: List[float] = field(default_factory=list)
    rounds: List[dict] = field(default_factory=list)
    prefills: List[dict] = field(default_factory=list)
    pause_s: float = 0.0
    compiles_in_window: int = 0
    done: list = field(default_factory=list)


def counters(dep) -> Dict[str, float]:
    st, cs = dep.engine.stats, dep.coe.cache.stats
    out = {f"serve.{k}": float(getattr(st, k)) for k in COUNTERS["serve"]}
    out.update({f"switch.{k}": float(getattr(cs, k))
                for k in COUNTERS["switch"]})
    return out


class _Compiles:
    """Counts XLA compilations (persistent-cache hits included) while on."""

    def __init__(self):
        self.on, self.n = False, 0
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **kw):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


_COMPILES: Optional[_Compiles] = None


def _annotate(obj, attr, name, log=None):
    """Wrap ``obj.attr`` in a profiler span named ``name``."""
    fn = getattr(obj, attr)

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            if log is not None:
                log(*a, **kw)
            return fn(*a, **kw)
    setattr(obj, attr, wrapped)


class _PrefillProxy:
    """Stands in for the engine's prefill runner to span and log each
    packed call (the engine calls the runner object itself)."""

    def __init__(self, runner, log):
        self._runner, self._log = runner, log

    def __getattr__(self, name):
        return getattr(self._runner, name)

    def __call__(self, params, prompts):
        self._log([len(p) for p in prompts])
        with jax.profiler.TraceAnnotation("bench.prefill"):
            return self._runner(params, prompts)


def instrument(dep, rec: Record, tracing: dict) -> None:
    """Spans around the engine's calls, and the per-round log of live lane
    lengths and per-prefill prompt lengths while ``tracing["on"]``."""
    eng, cache = dep.engine, dep.coe.cache

    def log_round(params, active):
        if tracing["on"]:
            lens = [eng.pool.length(eng.slots[i].req.rid) + 1
                    for i in np.nonzero(active)[0]]
            rec.rounds.append({"t": time.perf_counter(), "live": lens,
                               "n_slots": eng.n_slots})

    def log_prefill(lens):
        if tracing["on"]:
            rec.prefills.append({"t": time.perf_counter(), "lengths": lens})

    _annotate(cache, "activate", "bench.activate")
    _annotate(cache, "prefetch", "bench.prefetch")
    _annotate(eng.runner, "extend", "bench.extend")
    _annotate(eng.policy, "round", "bench.round", log_round)
    if eng.prefill_runner is not None:
        eng.prefill_runner = _PrefillProxy(eng.prefill_runner, log_prefill)


def run_window(dep, traffic: Traffic, seconds: float, trace_dir=None,
               trace_s: float = 4.0) -> Record:
    """Drive the engine through the window and the drain; returns the
    record. ``trace_dir`` turns on the profiler for ``trace_s`` seconds
    from 40% of the window."""
    global _COMPILES
    if _COMPILES is None:
        _COMPILES = _Compiles()
    eng = dep.engine
    p = traffic.params
    rec = Record(window_s=float(seconds))
    tracing = {"on": False}
    if trace_dir is not None:
        instrument(dep, rec, tracing)
    meta: Dict[int, dict] = {}          # rid -> bookkeeping
    live: Dict[int, object] = {}
    next_rid = [0]
    submit = eng.submit
    step = eng.step
    if trace_dir is not None:
        def submit(r, _s=eng.submit):
            with jax.profiler.TraceAnnotation("bench.submit"):
                _s(r)

        def step(_s=eng.step):
            with jax.profiler.TraceAnnotation("bench.step"):
                return _s()

    def send(item, arrival, client=None, in_window=True):
        from repro.serving import Request
        rid = next_rid[0]
        next_rid[0] += 1
        r = Request(rid=rid, tokens=item.prompt,
                    max_new_tokens=item.max_new_tokens, arrival_s=arrival,
                    expert=item.expert)
        meta[rid] = {"client": client, "in_window": in_window,
                     "paused": False}
        live[rid] = r
        submit(r)
        return r

    # closed loop: warm traffic first, so that slots have turned over
    cursor = [0] * len(traffic.clients)

    def client_next(c, now, in_window):
        if cursor[c] < len(traffic.clients[c]):
            send(traffic.clients[c][cursor[c]], now, c, in_window)
            cursor[c] += 1

    def finished(done, now, in_window):
        for r in done:
            live.pop(r.rid, None)
            rec.done.append(r)
            c = meta[r.rid]["client"]
            if c is not None and now < rec.t_end:
                client_next(c, now, in_window)

    closed = traffic.loop == "closed"
    if closed:
        t = time.perf_counter()
        for c in range(len(traffic.clients)):
            client_next(c, t, False)
        warm_end = t + float(p.get("warm_s", 0.0))
        rec.t_end = float("inf")
        while time.perf_counter() < warm_end:
            finished(step(), time.perf_counter(), False)

    rec.t0 = sched0 = time.perf_counter()
    rec.t_end = rec.t0 + seconds
    before = counters(dep)
    tok0 = eng.stats.tokens_out
    _COMPILES.on, _COMPILES.n = True, 0
    trace_at = rec.t0 + 0.4 * seconds if trace_dir is not None else None
    trace_s = min(trace_s, 0.5 * seconds)
    trace_stop = traced = t_start = None

    def stop_trace():
        """End the trace at a step boundary once the device is done;
        returns the seconds writing it out paused the loop."""
        dep.sync()
        t_stop = time.perf_counter()
        traced.__exit__(None, None, None)
        tracing["on"] = False
        jax.profiler.stop_trace()
        rec.pause_s = time.perf_counter() - t_stop
        for rid in live:
            meta[rid]["paused"] = True
        return rec.pause_s
    offsets = traffic.offsets_s
    i, n_items = 0, len(traffic.items)
    while True:
        now = time.perf_counter()
        if now >= rec.t_end:
            break
        if trace_at is not None and now >= trace_at:
            jax.profiler.start_trace(str(trace_dir))
            tracing["on"] = True
            traced = jax.profiler.TraceAnnotation("bench.traced")
            traced.__enter__()
            t_start = time.perf_counter()
            trace_stop, trace_at = t_start + trace_s, None
        if trace_stop is not None and now >= trace_stop:
            pause = stop_trace()
            rec.t_end += pause
            sched0 += pause           # the open-loop schedule shifts too
            trace_stop = None
            continue
        if not closed:
            while i < n_items and sched0 + offsets[i] <= now:
                arrival = sched0 + offsets[i]
                send(traffic.items[i], arrival)
                rec.lateness_s.append(time.perf_counter() - arrival)
                i += 1
        if eng.has_work:
            done = step()
            now = time.perf_counter()
            if now < rec.t_end:
                rec.tokens_in_window = eng.stats.tokens_out - tok0
            finished(done, now, True)
        elif not closed:
            nxt = sched0 + offsets[i] if i < n_items else rec.t_end
            time.sleep(max(0.0, min(nxt, rec.t_end) - time.perf_counter()))
        else:
            break
    if trace_stop is not None:
        stop_trace()
    _COMPILES.on = False
    rec.compiles_in_window = _COMPILES.n
    after = counters(dep)
    rec.counters = {k: after[k] - before[k] for k in after}
    cap = rec.t_end + float(p.get("drain_cap_s", 60.0))
    while eng.has_work and time.perf_counter() < cap:
        finished(step(), time.perf_counter(), True)
    done_by_rid = {r.rid: r for r in rec.done}
    for rid, m in meta.items():
        if m["in_window"]:
            r = done_by_rid.get(rid) or live[rid]
            rec.requests.append(request_record(r, m))
    return rec


def request_record(r, m) -> dict:
    return {"rid": r.rid, "expert": r.expert, "prompt_len": len(r.tokens),
            "max_new_tokens": r.max_new_tokens, "arrival": r.arrival_s,
            "submit": r.submit_s, "admit": r.admit_s,
            "first": r.first_token_s, "done": r.done_s,
            "n_out": 0 if r.output is None else len(r.output),
            "finished": r.done_s is not None, "paused": m["paused"]}
