"""Whole step: model FLOPs of the tokens processed in the traced window
(prefill and decode, attention at live lengths; ``bench/roofline/model.py``)
over the traced window times the chip's bf16 peak, in %."""
from benchlib import spec


def read(rec, ctx):
    red, peaks, m = ctx["trace"], ctx["peaks"], ctx["m"]
    if red is None or peaks is None or not (rec.rounds or rec.prefills):
        return None
    model = spec.roofline("model")
    flops = sum(model.decode_flops(m, r["live"]) for r in rec.rounds)
    flops += sum(model.prefill_flops(m, p["lengths"]) for p in rec.prefills)
    return 100.0 * flops / (red["window_s"] * peaks["bf16_flops_per_s"])
