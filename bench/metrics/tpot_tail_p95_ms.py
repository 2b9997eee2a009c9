"""Scheduler: 95th percentile of TPOT (ms) over the window's finished
requests not held by the trace pause: the lanes that waited longest,
through other experts' rounds and switches or through a stall of the
host. A few requests caught in one stall move it, so it stands beside
the steadier median of the end-to-end metrics."""
from benchlib.endtoend import pct, tpots_ms


def read(rec, ctx):
    v = tpots_ms([r for r in rec.requests if not r["paused"]])
    return pct(v, 95) if v else None
