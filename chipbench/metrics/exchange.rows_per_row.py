"""Rows the key-range exchange sent per input row (SpillStats
rows_exchanged, the mean over the window's queries)."""


def read(ctx):
    stats = ctx.window.counters.get("stats")
    if not stats or "queries" not in ctx.window.counters or ctx.chips < 2:
        return None
    n = ctx.window.counters["rows_per_query"]
    return sum(s.rows_exchanged for s in stats) / len(stats) / n
