"""Decode step: engine seconds per decode round during the window
(``serve.exec_s`` / ``serve.decode_rounds``), host clock, in ms."""


def read(rec, ctx):
    n = rec.counters["serve.decode_rounds"]
    return 1e3 * rec.counters["serve.exec_s"] / n if n else None
