"""Scheduler: mean over the window's decode rounds of active lanes over
slots (``serve.occupancy_sum`` / ``serve.decode_rounds``), in %."""


def read(rec, ctx):
    n = rec.counters["serve.decode_rounds"]
    return 100.0 * rec.counters["serve.occupancy_sum"] / n if n else None
