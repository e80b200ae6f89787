"""Device seconds of the merge-on-read program (_finalize_stream) per
snapshot or close (profiler trace)."""


def read(ctx):
    answers = ctx.window.counters.get("answers")
    if ctx.trace is None or not answers:
        return None
    s = ctx.trace.module_seconds("_finalize_stream")
    return None if s is None else s / answers
