"""Scheduler: 95th percentile of admission minus scheduled arrival (s),
over the window's requests that were admitted and not held by the trace
pause."""
from benchlib.endtoend import pct


def read(rec, ctx):
    w = [r["admit"] - r["arrival"] for r in rec.requests
         if r["admit"] is not None and not r["paused"]]
    return pct(w, 95) if w else None
