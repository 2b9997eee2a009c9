"""Weight tier: bytes copied host->HBM over the seconds the copies took,
during the window (``switch.bytes_copied_in`` / ``switch.h2d_seconds``),
in GB/s."""


def read(rec, ctx):
    b, s = (rec.counters["switch.bytes_copied_in"],
            rec.counters["switch.h2d_seconds"])
    return b / s / 1e9 if b and s > 0 else None
