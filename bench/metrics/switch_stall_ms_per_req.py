"""Weight tier: the seconds the engine stalled in ``activate`` during the
window (``switch.switch_seconds``), per request finished in it (ms)."""


def read(rec, ctx):
    n = sum(r["finished"] and r["done"] <= rec.t_end for r in rec.requests)
    return 1e3 * rec.counters["switch.switch_seconds"] / n if n else None
