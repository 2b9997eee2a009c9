"""Whole step: model FLOPs of the tokens processed in the traced window
(prefill and decode, attention at live lengths; the architecture's
``decode_flops`` and ``prefill_flops``, ``bench/arch/<model_type>.py``) over
the traced window times the chip's bf16 peak, in %."""
from benchlib import BENCH_DIR, spec


def read(rec, ctx):
    red, peaks, m = ctx["trace"], ctx["peaks"], ctx["m"]
    if red is None or peaks is None or not (rec.rounds or rec.prefills):
        return None
    arch = spec.arch(m, ctx.get("bench", BENCH_DIR))
    flops = sum(arch.decode_flops(m, r["live"]) for r in rec.rounds)
    flops += sum(arch.prefill_flops(m, p["lengths"]) for p in rec.prefills)
    return 100.0 * flops / (red["window_s"] * peaks["bf16_flops_per_s"])
