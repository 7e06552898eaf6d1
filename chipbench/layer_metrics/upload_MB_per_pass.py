"""Encode / upload: megabytes of encoded columns a pass put into the HBM
column cache, from the cache's own ``stats()`` around each query (the
trace names host-to-device transfers but a resident pass has none to learn
their names from; PERF.md, Open questions). 0 in a resident cell."""

import statistics


def read(ctx):
    return statistics.median(p.uploaded_bytes for p in ctx.passes) / 1e6
