"""Dispatch gate: of the tables of the traced passes' filtered scans that
ended in rows (a scan under a join, not under a fused aggregate), the share
whose filter the device's selection program ran over the table's encoded
columns, against those the reader filtered on the host: the program's
tally on each query's trace (``summary()["selects"]``: ``tables_device`` /
``tables_host``). 0 where the gate kept every table on the host. None when
the program tallies neither (the parent of PR 41), or no traced pass ran
such a scan."""

from chipbench import program_spans


def selects_of(ctx):
    """Per traced pass, the ``selects`` tallies of its queries in the
    order they ran (one dict a query whose summary has them); None when
    no pass holds a summary."""
    summaries = program_spans.finished()
    traced = program_spans.by_pass(ctx.passes, summaries) if summaries \
        else []
    if not traced:
        return None
    return [[s.get("selects") for s in sorted(
        inside, key=lambda s: s.get("t0_perf_s", 0.0))]
        for inside in traced]


def total(ctx, key):
    """``key`` of the tallies added over the traced passes, or None when
    nothing tallies it."""
    passes = selects_of(ctx)
    if passes is None:
        return None
    found = [sel[key] for inside in passes for sel in inside
             if sel is not None and key in sel]
    return sum(found) if found else None


def read(ctx):
    device, host = total(ctx, "tables_device"), total(ctx, "tables_host")
    if device is None or host is None or not device + host:
        return None
    return 100.0 * device / (device + host)
