"""Paged decode attention's share of its roofline (%) over the traced
window: ``bench/roofline/decode_paged.py`` over the kernel's device time."""
from benchlib.rooflines import share


def read(rec, ctx):
    return share(rec, ctx, "decode_paged")
