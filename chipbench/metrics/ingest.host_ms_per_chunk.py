"""Host milliseconds per ingest() call: staging (pack, pad, device_put)
and the dispatch of the previous chunk's absorb, timed around each call."""


def read(ctx):
    chunks = ctx.window.counters.get("chunks")
    if not chunks:
        return None
    return 1e3 * ctx.window.counters["ingest_host_s"] / chunks
