"""The fused one-shot query program's share of its HBM roofline.

The least traffic any implementation moves for one query is reading the
N input rows once and writing the G output rows once.  That over the
HBM peak of the chips used is the least time a query can take; this is
that time over the device busy seconds per query in the trace, so it
cannot pass 100%."""


def read(ctx):
    queries = ctx.window.counters.get("queries")
    if ctx.trace is None or not queries or ctx.trace.busy_s <= 0:
        return None
    q = ctx.query
    least_bytes = q.rows * q.input_row_bytes() + ctx.groups * q.output_row_bytes()
    least_s = least_bytes / (ctx.chips * ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ctx.trace.busy_s / queries)
