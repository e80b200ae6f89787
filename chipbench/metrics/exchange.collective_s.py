"""Device seconds of the all-to-all ops per query, the mean over the
chips (profiler trace)."""


def read(ctx):
    queries = ctx.window.counters.get("queries")
    if ctx.trace is None or not queries:
        return None
    s = ctx.trace.op_seconds("all-to-all")
    return None if s is None else s / queries
