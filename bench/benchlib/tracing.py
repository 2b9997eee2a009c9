"""Reduction of a profiler trace to device busy time, time per device
operation, and idle gaps attributed to what the host was doing.

Two stages. ``extract`` reads the ``.xplane.pb`` that ``jax.profiler``
wrote (with ``jax.profiler.ProfileData``, nothing else) into plain lists:
the device operations of each chip, each under a short label made from its
HLO text (``label``), and the harness's host spans (names starting
``bench.``). ``reduce`` works on those lists only, so a small recorded trace
can be kept as JSON and the arithmetic tested on it.

The traced window is the harness's ``bench.traced`` span. Busy is the union
of the intervals in which an operation ran on a chip, clipped to the
window, averaged over chips. An operation's time is its own: the time of
the operations nested inside it (the body of a ``while``) is taken off its
parent's, so the seconds per operation add up to the busy time. Each idle
gap of a chip is attributed to the innermost ``bench.*`` span open on the
host at the gap's midpoint, or to ``host:none`` when the loop had none
open (waiting for an arrival).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Callable, Dict, List, Optional

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
TARGET = re.compile(r'custom_call_target="([^"]+)"')
LAYOUT = re.compile(r"\{[^{}]*\}")


def xplane_path(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def _split_type(rest: str):
    """``(type, remainder)`` of the text after ``%name = ``; a tuple type
    is skipped to its closing parenthesis."""
    if not rest.startswith("("):
        head, _, tail = rest.partition(" ")
        return head, tail
    depth = 0
    for i, ch in enumerate(rest):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            return "(tuple)", rest[i + 2:]
    return "(tuple)", ""


def label(hlo: str) -> str:
    """Short label of a device operation from its HLO text:
    ``<instruction> <opcode or custom-call target> <result type>``, and for
    a Pallas kernel (``tpu_custom_call``) its operand types, layouts and
    memory spaces left out. Text that is not an HLO instruction is kept."""
    name, sep, rest = hlo.partition(" = ")
    if not sep or not name.startswith("%"):
        return hlo
    rtype, tail = _split_type(rest)
    op = tail.partition("(")[0]
    out = f"{name[1:]} {op} {LAYOUT.sub('', rtype)}"
    t = TARGET.search(tail)
    if t:
        out = f"{name[1:]} {t.group(1)} {LAYOUT.sub('', rtype)}"
        if t.group(1) == "tpu_custom_call":
            args = LAYOUT.sub("", tail).partition("(")[2].partition(")")[0]
            types = [a.strip().split(" ")[0] for a in args.split(", ")
                     if a.strip()]
            out += " (" + ",".join(types) + ")"
    return out


def extract(path: str) -> dict:
    """``{"devices": {id: [[label, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...]}`` from an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[list]] = {}
    host: List[list] = []
    labels: Dict[str, str] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops = devices.setdefault(m.group(2), [])
                for e in line.events:
                    n = e.name
                    if n not in labels:
                        labels[n] = label(n)
                    ops.append([labels[n], int(e.start_ns),
                                int(e.duration_ns)])
            elif not m:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"devices": devices, "host": host}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _own_times(clipped: List[tuple]) -> List[tuple]:
    """``[(name, own_ns)]`` of clipped ``(name, a, b)`` intervals that nest:
    each loses the time of the intervals directly inside it."""
    order = sorted(range(len(clipped)),
                   key=lambda i: (clipped[i][1], -clipped[i][2]))
    own = [b - a for _, a, b in clipped]
    stack: List[int] = []
    for i in order:
        _, a, b = clipped[i]
        while stack and clipped[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(b, clipped[stack[-1]][2]) - a
        stack.append(i)
    return [(clipped[i][0], own[i]) for i in range(len(clipped))]


def _attribute(mid: float, spans: List[list]) -> str:
    best, best_len = "host:none", None
    for name, s, d in spans:
        if s <= mid < s + d and (best_len is None or d < best_len):
            best, best_len = name, d
    return best


def reduce(ev: dict) -> Optional[dict]:
    """Busy and idle seconds, own seconds and calls per device operation,
    and idle seconds by host span, over the traced window; None without
    one."""
    window = [h for h in ev["host"] if h[0] == "bench.traced"]
    if not window or not ev["devices"]:
        return None
    _, w0, wd = window[0]
    w1 = w0 + wd
    spans = [h for h in ev["host"] if h[0] != "bench.traced"]
    busy_ns, ops, calls, gaps, n_gaps = 0, {}, {}, {}, 0
    for dev_ops in ev["devices"].values():
        clipped = []
        for name, s, d in dev_ops:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((name, a, b))
                calls[name] = calls.get(name, 0) + 1
        for name, t in _own_times(clipped):
            ops[name] = ops.get(name, 0) + t
        merged = _union([(a, b) for _, a, b in clipped])
        busy_ns += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                label_ = _attribute((a + b) / 2, spans)
                gaps[label_] = gaps.get(label_, 0) + (b - a)
                n_gaps += 1
    n_dev = len(ev["devices"])
    return {"window_s": wd / 1e9, "busy_s": busy_ns / n_dev / 1e9,
            "ops": {k: v / n_dev / 1e9 for k, v in ops.items()},
            "calls": {k: v / n_dev for k, v in calls.items()},
            "gaps": {k: v / n_dev / 1e9 for k, v in gaps.items()},
            "n_gaps": n_gaps}


def kernel_seconds(red: dict, match: Callable[[str], bool]) -> float:
    """Device seconds of the operations whose label ``match`` accepts."""
    return sum(v for k, v in red["ops"].items() if match(k))


def breakdown(red: dict, names: Optional[Dict[str, str]] = None,
              top: int = 10) -> dict:
    """The ``top`` device operations by own time and the ``top`` idle gaps
    by host span. ``names`` maps an operation's label to the kernel it
    runs, which then heads the label."""
    names = names or {}
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["gaps"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[f"{names[k]}: {k}" if k in names else k, v]
                           for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
