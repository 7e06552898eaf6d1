"""Dispatch gate: of the scan tasks the device tier's scan path
resolved during the traced passes, the share whose table ran on the device,
served from the HBM cache or encoded now, against those left to the host
(the program's tally on each query's trace). Counts the cache hits
that ``device_decisions_pct`` cannot see."""

from chipbench import program_spans


def read(ctx):
    t = program_spans.totals(ctx)
    if t is None:
        return None
    on_device = t["from_cache"] + t["encoded"]
    total = on_device + t["host"]
    return 100.0 * on_device / total if total else None
