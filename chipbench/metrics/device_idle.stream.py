"""Device idle share of the stream window: 1 - busy / window, the mean
over the chips used (profiler trace)."""


def read(ctx):
    return None if ctx.trace is None else 100.0 * ctx.trace.idle_share
