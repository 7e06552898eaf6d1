"""API / plan: milliseconds a pass spends in the query builders (file
listing, Parquet footers, the logical plan), median over the passes; the
benchmark's own span around ``queries/<q>.build``."""

import statistics


def read(ctx):
    return 1e3 * statistics.median(p.plan_s for p in ctx.passes)
