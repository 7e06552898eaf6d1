"""Executor: of the rows the traced passes' filtered scans that ended in
rows held, the share their filters kept, tallied alike where the device's
selection program ran the filter and where the reader did
(``summary()["selects"]``: ``rows_out`` / ``rows_in``). It is the
selectivity the gate's bet is compared with, and what the packed fetch
carries. None when the program tallies neither (the parent of PR 41), or
no traced pass ran such a scan."""

from chipbench.layer_metrics import select_device_tables_pct as tables


def read(ctx):
    rows_in, rows_out = tables.total(ctx, "rows_in"), \
        tables.total(ctx, "rows_out")
    if not rows_in or rows_out is None:
        return None
    return 100.0 * rows_out / rows_in
