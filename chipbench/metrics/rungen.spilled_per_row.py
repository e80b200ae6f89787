"""Rows run generation spilled per input row (SpillStats, the mean over
the window's queries)."""


def read(ctx):
    stats = ctx.window.counters.get("stats")
    if not stats or "queries" not in ctx.window.counters:
        return None
    n = ctx.window.counters["rows_per_query"]
    return sum(s.rows_spilled_run_generation for s in stats) / len(stats) / n
