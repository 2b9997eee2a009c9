"""Prefill: engine seconds in prefill during the window
(``serve.prefill_s``) per 1000 prompt tokens admitted in it (ms)."""


def read(rec, ctx):
    toks = sum(r["prompt_len"] for r in rec.requests
               if r["admit"] is not None and rec.t0 <= r["admit"] < rec.t_end)
    s = rec.counters["serve.prefill_s"]
    return 1e3 * s / (toks / 1e3) if toks and s > 0 else None
