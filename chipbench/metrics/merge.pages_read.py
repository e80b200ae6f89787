"""Pages the pre-merge levels and the wide merge read per query
(SpillStats, the mean over the window's queries)."""


def read(ctx):
    stats = ctx.window.counters.get("stats")
    if not stats or "queries" not in ctx.window.counters:
        return None
    return sum(s.pages_read for s in stats) / len(stats)
