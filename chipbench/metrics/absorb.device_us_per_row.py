"""Device microseconds of the streamed absorb program (_absorb_chunk) per
row ingested (profiler trace)."""


def read(ctx):
    rows = ctx.window.counters.get("rows_ingested")
    if ctx.trace is None or not rows:
        return None
    s = ctx.trace.module_seconds("_absorb_chunk")
    return None if s is None else 1e6 * s / rows
