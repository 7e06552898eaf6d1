"""Scan / decode: the rows a pass's scans loaded on the host, in millions:
the ``rows`` of the program's ``scan:load`` spans (a batch as the reader
hands it over, after the scan's own pushed-down filter; a table the HBM
column cache serves loads nothing), added over a pass's queries, median
over the traced passes. It is what the host decodes, exchanges and probes
whatever a join keeps of it afterwards: where a fact table's only filter
is a join's key set, every row of it, once a scan of the plan (~120 in
``tpch-sf10.part-lookup``: 60 M rows, scanned twice a Q17). None when no
traced pass holds a summary."""

from chipbench import program_spans


def read(ctx):
    phases = program_spans.per_pass(ctx)
    if phases is None:
        return None
    return phases.get("scan:load", {}).get("rows", 0) / 1e6
