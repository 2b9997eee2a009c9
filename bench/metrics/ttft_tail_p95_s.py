"""Scheduler: 95th percentile of TTFT (s), arrival to first token, over the
window's requests not held by the trace pause. In a cell of a few dozen
open-loop requests on a rotating CoE scheduler this tail is set by the
order of arrivals and spreads too widely between seeds to bound end to end;
it stands here beside the cell's steadier end-to-end metrics."""
from benchlib.endtoend import pct, ttft


def read(rec, ctx):
    v = [ttft(r, ctx["drain_end"]) for r in rec.requests if not r["paused"]]
    return pct(v, 95) if v else None
