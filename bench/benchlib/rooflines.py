"""Shared arithmetic of the ``*_roofline`` readers: a kernel's share of its
roofline over the traced window.

Each ``bench/roofline/<kernel>.py`` names one Pallas kernel: ``match(op,
m)`` tells from an operation's parsed label (``parse``) and the
configuration whether the operation runs that kernel, by the shapes the
kernel takes, so the reading does not hang on the names XLA gives
instructions. ``cost(m, live, n_slots)`` gives the FLOPs and bytes that one
call of the kernel in a decode round needs. A configuration's architecture
(``spec.arch``) lists the kernels it runs (``KERNELS``) and how many calls
of each one decode round makes (``calls_per_round``).

For each traced decode round, the least time the chip could take for the
round's calls is their number times the larger of FLOPs over peak FLOP/s
and bytes over peak bytes/s; their sum over the kernel's device time in the
trace is the share, in %."""
from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional

from benchlib import BENCH_DIR, spec

_LABEL = re.compile(r"^(\S+) (\S+) (\S+)(?: \((.*)\))?$")


class Op(NamedTuple):
    name: str           # HLO instruction name, e.g. ``decode_paged.3``
    target: str         # opcode or custom-call target
    result: str         # result type, e.g. ``bf16[32,4096]``
    operands: List[str]  # operand types of a Pallas kernel, else []


def parse(label: str) -> Optional[Op]:
    m = _LABEL.match(label)
    if not m:
        return None
    ops = m.group(4).split(",") if m.group(4) else []
    # operand types hold commas themselves: rejoin ``bf16[a`` ``b]``
    out, cur = [], ""
    for part in ops:
        cur = f"{cur},{part}" if cur else part
        if cur.count("[") == cur.count("]"):
            out.append(cur)
            cur = ""
    return Op(m.group(1), m.group(2), m.group(3), out)


def shape(t: str) -> tuple:
    """``bf16[4,128]`` -> ``("bf16", (4, 128))``."""
    dt, _, dims = t.partition("[")
    dims = dims.rstrip("]")
    return dt, tuple(int(d) for d in dims.split(",") if d)


def kernels(m: dict, bench: Path = BENCH_DIR) -> Dict[str, object]:
    """The roofline module of each kernel ``m``'s architecture lists, by
    name."""
    return {k: spec.roofline(k, bench) for k in spec.arch(m, bench).KERNELS}


def names(red: dict, m: dict, bench: Path = BENCH_DIR) -> Dict[str, str]:
    """Label -> kernel, for every traced operation that a roofline module
    of ``m``'s architecture names."""
    mods = kernels(m, bench)
    out = {}
    for lab in red["ops"]:
        op = parse(lab)
        if op is None or op.target != "tpu_custom_call":
            continue
        for k, mod in mods.items():
            if mod.match(op, m):
                out[lab] = k
    return out


def share(rec, ctx, kernel: str) -> Optional[float]:
    red, peaks, m = ctx["trace"], ctx["peaks"], ctx["m"]
    if red is None or peaks is None or not rec.rounds:
        return None
    bench = ctx.get("bench", BENCH_DIR)
    mod, arch = spec.roofline(kernel, bench), spec.arch(m, bench)
    seconds = sum(v for k, v in red["ops"].items()
                  if ctx["kernels"].get(k) == kernel)
    if seconds <= 0:
        return None
    calls = arch.calls_per_round(m, kernel)
    least = 0.0
    for r in rec.rounds:
        flops, nbytes = mod.cost(m, r["live"], r["n_slots"])
        least += calls * max(
            flops / peaks["bf16_flops_per_s"],
            nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
