"""Attribution of a profiler trace to the program's own spans and named
scopes: what the host was doing in each idle gap of the chip, and which
part of which program each device second went to.

The program under test writes its spans (``repro.obs.trace``) into the
profile as ``TraceAnnotation`` events named ``<category>.<name>``
(``engine.decode``, ``switch.activate``), on the thread that ran them;
its jitted programs are named (``decode_step``, ``sample``,
``prefill_packed``) and the decode step's parts carry ``jax.named_scope``
names in each operation's ``op_name`` (``SCOPES``).

Two stages, as in ``tracing``. ``extract`` reads the ``.xplane.pb`` into
what ``tracing.extract`` gives (``devices``, ``host``) plus
``program_host`` (``[name, start_ns, dur_ns, thread]`` of every program
span), ``op_meta`` (``[program, scope]`` of each device operation, in
the order of ``devices``) and ``device_programs`` (``[program, start_ns,
dur_ns]`` of each program run on each chip). ``reduce`` works on those lists only, so a
small recorded trace can be kept as JSON and tested.

A device operation's program is the ``XLA Modules`` event running on its
chip when it started (``jit_decode_step(<fingerprint>)``); no stat of a
TPU operation event names it. Its scope is the innermost of ``SCOPES``
among the parts of its ``op_name``. The event carries no ``op_name``
either (its name is the instruction's HLO text without the metadata), so
``extract`` takes the compiled programs' HLO text (``hlo``, by program)
and looks the instruction up there by name. The operations of
``decode_step`` that no scope owns (the layer scan's slicing and writing
back of its carried arrays, and the copies XLA makes of them) are filed
as ``decode_step/(scan carry)``.

Idle time is split exactly: each idle stretch of a chip goes, piece by
piece, to the innermost program span open on the engine's thread (the
thread that ran ``engine.step``) over that piece, or to ``host:none``.
Program spans open on other threads over an idle piece (the weight tier's
``switch.store_read`` and ``switch.h2d`` workers) are reported apart, as
overlap. Idle time is also split by the program running on the chip over
it: a program can leave the chip without an operation while it runs
(waiting on a transfer), and the host can wait while no program runs.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Tuple

from benchlib import tracing

CATEGORIES = ("engine.", "kernel.", "switch.", "store.", "node.")
SCOPES = ("embed", "qkv_rope", "kv_scatter", "attention", "oproj_ffn",
          "head")
STEP = "decode_step"
# the scope each fused kernel of the decode step runs under
KERNEL_SCOPES = {"qkv_rope_paged": "qkv_rope", "decode_paged": "attention",
                 "oproj_ffn_swiglu": "oproj_ffn"}
CARRY = f"{STEP}/(scan carry)"
NONE = "(none)"
BETWEEN = "(no program)"
MODULES_LINE = "XLA Modules"
INSTR_OP_NAME = re.compile(r'^\s*(?:ROOT )?%(\S+) = .*?op_name="([^"]*)"',
                           re.M)
MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(-?\d+\))?$")


def program_of(module: str) -> str:
    """``jit_decode_step(123)`` -> ``decode_step``."""
    return MODULE.match(module.strip()).group(1)


def scope_of(op_name: str) -> Optional[str]:
    """The innermost of ``SCOPES`` among the ``/``-parts of an
    ``op_name``, or None."""
    parts = [p for p in op_name.split("/") if p in SCOPES]
    return parts[-1] if parts else None


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` of a compiled program's HLO text
    (``Compiled.as_text()``)."""
    return dict(INSTR_OP_NAME.findall(hlo_text))


def _op_name(name: str, known: Dict[str, str]) -> str:
    """The ``op_name`` of the instruction whose HLO text is ``name``."""
    return known.get(name.partition(" = ")[0].lstrip("%"), "")


def _module_at(modules: List[tuple], starts: List[int], t: int) -> str:
    """Name of the module event ``(start, end, name)`` that covers ``t``
    (``modules`` sorted by start, ``starts`` their starts), or ``""``."""
    i = bisect.bisect_right(starts, t) - 1
    return modules[i][2] if i >= 0 and t < modules[i][1] else ""


def extract(path: str, hlo: Optional[Dict[str, str]] = None) -> dict:
    """``tracing.extract(path)`` plus ``program_host``, ``op_meta`` and
    ``device_programs``. ``hlo`` maps a program's name (``decode_step``) to its compiled HLO
    text, where operations find their ``op_name``."""
    from jax.profiler import ProfileData
    known = {k: op_names(v) for k, v in (hlo or {}).items()}
    ev = tracing.extract(path)
    pd = ProfileData.from_file(path)
    program_host: List[list] = []
    op_meta: Dict[str, List[list]] = {}
    runs: Dict[str, List[list]] = {}
    for plane in pd.planes:
        m = tracing.DEVICE_PLANE.match(plane.name)
        if not m:
            for i, line in enumerate(plane.lines):
                thread = f"{line.name}/{i}"
                program_host.extend(
                    [e.name, int(e.start_ns), int(e.duration_ns), thread]
                    for e in line.events if e.name.startswith(CATEGORIES))
            continue
        lines = list(plane.lines)
        modules = sorted(
            (int(e.start_ns), int(e.start_ns) + int(e.duration_ns),
             program_of(e.name))
            for ln in lines if ln.name == MODULES_LINE for e in ln.events)
        starts = [a for a, _, _ in modules]
        runs[m.group(2)] = [[p, a, b - a] for a, b, p in modules]
        meta = op_meta.setdefault(m.group(2), [])
        for line in lines:
            if line.name != tracing.OPS_LINE:
                continue
            for e in line.events:
                prog = _module_at(modules, starts, int(e.start_ns))
                on = _op_name(e.name, known.get(prog, {}))
                meta.append([prog or NONE, scope_of(on) or ""])
    for dev, ops in ev["devices"].items():
        if len(op_meta.get(dev, [])) != len(ops):
            raise ValueError(f"device {dev}: {len(ops)} operations but "
                             f"{len(op_meta.get(dev, []))} attributions")
    ev["program_host"] = program_host
    ev["op_meta"] = op_meta
    ev["device_programs"] = runs
    return ev


def trim(ev: dict, a: int, b: int) -> dict:
    """The events of ``ev`` that overlap ``[a, b)``, with the traced
    window cut to it."""
    over = lambda x: x[1] < b and x[1] + x[2] > a
    devices, meta = {}, {}
    for d, ops in ev["devices"].items():
        keep = [i for i, x in enumerate(ops) if over(x)]
        devices[d] = [ops[i] for i in keep]
        meta[d] = [ev["op_meta"][d][i] for i in keep]
    return {"devices": devices,
            "host": [["bench.traced", a, b - a]]
            + [x for x in ev["host"] if x[0] != "bench.traced" and over(x)],
            "program_host": [x for x in ev["program_host"] if over(x)],
            "op_meta": meta,
            "device_programs": {d: [x for x in r if over(x)]
                                for d, r in ev["device_programs"].items()}}


# ----------------------------------------------------------------------
# reduction
# ----------------------------------------------------------------------

def _timeline(spans: List[list], w0: int, w1: int
              ) -> List[Tuple[int, int, tuple]]:
    """Pieces ``(a, b, stack)`` of ``[w0, w1)`` over which the names of the
    spans of one thread open there (outermost first) stay the same."""
    clipped = sorted(((max(s, w0), min(s + d, w1), name)
                      for name, s, d, *_ in spans
                      if min(s + d, w1) > max(s, w0)),
                     key=lambda x: (x[0], -x[1]))
    bounds = sorted({w0, w1} | {x for a, b, _ in clipped for x in (a, b)})
    out, stack, j = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        stack = [x for x in stack if x[1] > a]
        while j < len(clipped) and clipped[j][0] <= a:
            stack.append(clipped[j])
            j += 1
        out.append((a, b, tuple(x[2] for x in stack)))
    return out


def _intersect(pieces, intervals) -> List[tuple]:
    """``(a, b, stack, label)`` of each piece's overlap with the sorted,
    disjoint ``intervals`` ``(a, b, label)``."""
    out, i = [], 0
    for a, b, stack in pieces:
        while i < len(intervals) and intervals[i][1] <= a:
            i += 1
        k = i
        while k < len(intervals) and intervals[k][0] < b:
            lo, hi = max(a, intervals[k][0]), min(b, intervals[k][1])
            if hi > lo:
                out.append((lo, hi, stack, intervals[k][2]))
            k += 1
    return out


def _gaps(ops, w0: int, w1: int) -> List[Tuple[int, int]]:
    merged = tracing._union([(max(s, w0), min(s + d, w1))
                             for _, s, d in ops if min(s + d, w1) > max(s, w0)])
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def engine_thread(program_host: List[list]) -> Optional[str]:
    """The thread that ran most ``engine.step`` spans."""
    n: Dict[str, int] = {}
    for name, _, _, th in program_host:
        if name == "engine.step":
            n[th] = n.get(th, 0) + 1
    return max(n, key=n.get) if n else None


def reduce(ev: dict) -> Optional[dict]:
    """Idle seconds by program span, spans counted by name and device
    seconds by (program, scope) over the traced window; None without a
    window or without program spans.

    ``program_gaps``: idle seconds by the innermost program span open on
    the engine's thread (``host:none`` where none is). ``step_children``:
    the same idle seconds by the child of ``engine.step`` they fell in
    (``engine.step`` itself for its own time, ``outside`` when no
    ``engine.step`` was open). ``worker_overlap``: idle seconds during
    which a span was open on another thread, by its innermost span.
    ``idle_by_program``: idle seconds by the program running on the chip
    over them (``BETWEEN`` where none ran), and ``span_program_gaps`` the
    same crossed with ``program_gaps`` (``<span>|<program>``).
    ``program_counts``: spans of each name that overlap the window.
    ``scope_ops``: own device seconds by ``<program>/<scope>``."""
    window = [h for h in ev["host"] if h[0] == "bench.traced"]
    spans = ev.get("program_host") or []
    eng = engine_thread(spans)
    if not window or not ev["devices"] or eng is None:
        return None
    _, w0, wd = window[0]
    w1 = w0 + wd
    n_dev = len(ev["devices"])
    mine = [x for x in spans if x[3] == eng]
    pieces = _timeline(mine, w0, w1)
    by_thread: Dict[str, list] = {}
    for x in spans:
        if x[3] != eng:
            by_thread.setdefault(x[3], []).append(x)
    others = [_timeline(v, w0, w1) for v in by_thread.values()]

    gaps_by: Dict[str, float] = {}
    children: Dict[str, float] = {}
    overlap: Dict[str, float] = {}
    running: Dict[str, float] = {}
    crossed: Dict[str, float] = {}
    add = lambda d, k, ns: d.__setitem__(k, d.get(k, 0) + ns)
    idle_ns = 0
    scope_ops: Dict[str, float] = {}
    for dev, ops in ev["devices"].items():
        gaps = [(a, b, None) for a, b in _gaps(ops, w0, w1)]
        idle_ns += sum(b - a for a, b, _ in gaps)
        runs = _timeline(ev.get("device_programs", {}).get(dev, []), w0, w1)
        idle = [(a, b, st[-1] if st else BETWEEN)
                for a, b, st, _ in _intersect(runs, gaps)]
        for a, b, stack, prog in _intersect(pieces, idle):
            ns = b - a
            inner = stack[-1] if stack else "host:none"
            add(gaps_by, inner, ns)
            add(running, prog, ns)
            add(crossed, f"{inner}|{prog}", ns)
            child = "outside"
            if "engine.step" in stack:
                i = stack.index("engine.step")
                child = stack[i + 1] if i + 1 < len(stack) else "engine.step"
            add(children, child, ns)
        for tl in others:
            for a, b, stack, _ in _intersect(tl, gaps):
                if stack:
                    add(overlap, stack[-1], b - a)
        meta = ev["op_meta"][dev]
        clipped, keys = [], []
        for (name, s, d), (prog, scope) in zip(ops, meta):
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((name, a, b))
                keys.append(f"{prog}/{scope}" if scope else (
                    CARRY if prog == STEP else prog))
        for key, (_, t) in zip(keys, tracing._own_times(clipped)):
            scope_ops[key] = scope_ops.get(key, 0) + t
    counts: Dict[str, int] = {}
    for name, s, d, _ in spans:
        if s < w1 and s + d > w0:
            counts[name] = counts.get(name, 0) + 1
    sec = lambda d: {k: v / n_dev / 1e9 for k, v in d.items()}
    return {"window_s": wd / 1e9, "idle_s": idle_ns / n_dev / 1e9,
            "program_gaps": sec(gaps_by), "step_children": sec(children),
            "worker_overlap": sec(overlap), "idle_by_program": sec(running),
            "span_program_gaps": sec(crossed), "program_counts": counts,
            "scope_ops": sec(scope_ops)}


# ----------------------------------------------------------------------
# per-layer readings
# ----------------------------------------------------------------------

SCHED = ("engine.step", "engine.pick", "engine.admission",
         "engine.prefetch_next")


def decode_host_gap_ms(pr: Optional[dict]) -> Optional[float]:
    """Decode step: idle ms inside ``engine.decode`` and its children, per
    traced ``engine.decode`` span."""
    n = pr and pr["program_counts"].get("engine.decode")
    if not n:
        return None
    return 1e3 * pr["step_children"].get("engine.decode", 0.0) / n


def sched_host_gap_ms(pr: Optional[dict]) -> Optional[float]:
    """Scheduler: idle ms inside ``engine.step``'s own time,
    ``engine.pick``, ``engine.admission`` and ``engine.prefetch_next``
    (with their children), per traced ``engine.step`` span."""
    n = pr and pr["program_counts"].get("engine.step")
    if not n:
        return None
    return 1e3 * sum(pr["step_children"].get(k, 0.0) for k in SCHED) / n


def decode_scan_copy_share(pr: Optional[dict], busy_s: float
                           ) -> Optional[float]:
    """Decode step (device): % of busy seconds in
    ``decode_step/(scan carry)``."""
    if not pr or busy_s <= 0 or not any(
            k.startswith(f"{STEP}/") for k in pr["scope_ops"]):
        return None
    return 100.0 * pr["scope_ops"].get(CARRY, 0.0) / busy_s


def summary(pr: Optional[dict], red: Optional[dict]) -> dict:
    """The three readings of one traced window, ``None`` where the trace
    holds nothing to read."""
    busy = red["busy_s"] if red else 0.0
    return {"decode_host_gap_ms": decode_host_gap_ms(pr),
            "sched_host_gap_ms": sched_host_gap_ms(pr),
            "decode_scan_copy_share": decode_scan_copy_share(pr, busy)}
