"""End-to-end metrics, taken by the benchmark from its own clock and the
device's allocator: exact percentiles over the window's requests, timed
from each request's scheduled arrival."""
from __future__ import annotations

from typing import List

import numpy as np


def pct(values: List[float], q: float) -> float:
    """The ``q``-th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttft(req: dict, never: float) -> float:
    """Arrival to first token; a request that never got one counts until
    ``never`` (the end of the drain)."""
    first = req["first"] if req["first"] is not None else never
    return first - req["arrival"]


def tpot(req: dict) -> float:
    return (req["done"] - req["first"]) / (req["n_out"] - 1)


def tpots_ms(requests) -> List[float]:
    """TPOT of every finished request with more than one output token."""
    return [1e3 * tpot(r) for r in requests
            if r["finished"] and r["n_out"] > 1]


def tpot_p50_ms(rec, ctx) -> float:
    return pct(tpots_ms(rec.requests), 50)


def tokens_per_s(rec, ctx) -> float:
    """Output tokens emitted inside the window, over the window."""
    return rec.tokens_in_window / rec.window_s


def peak_hbm_gb(rec, ctx) -> float:
    return ctx["peak_bytes"] / 1e9


def setup_s(rec, ctx) -> float:
    return rec.t0 - ctx["start"]


METRICS = {f.__name__: f for f in (tpot_p50_ms, tokens_per_s, peak_hbm_gb,
                                    setup_s)}
