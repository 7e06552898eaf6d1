"""Device programs: the fused scan-aggregate programs' share of the HBM
roofline. Bytes: for each query marked ``FUSED_SCAN_AGG`` whose ``execute``
spans saw the traffic's ``agg_programs`` run, rows in the files x the least
bytes its columns take (``peaks.scan_agg_bytes``), once per span. Time: the
device seconds of those runs in the trace. Memory-bound: the arithmetic is
a few flops per value."""

from chipbench import peaks


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    programs = set(ctx.traffic.get("agg_programs", ()))
    nbytes = seconds = 0.0
    for q, mod in ctx.queries.items():
        if not getattr(mod, "FUSED_SCAN_AGG", False):
            continue
        ran = sum(s for name, s in ctx.trace.span_module_s.get(
            f"execute:{q}", {}).items() if name in programs)
        if not ran:
            continue
        seconds += ran
        for table, columns in mod.SCANS.items():
            nbytes += (ctx.trace.span_count[f"execute:{q}"]
                       * peaks.scan_agg_bytes(ctx.table_rows[table],
                                              list(columns.values())))
    if not seconds:
        return None
    return 100.0 * (nbytes / ctx.peaks["hbm_bps"]) / seconds
