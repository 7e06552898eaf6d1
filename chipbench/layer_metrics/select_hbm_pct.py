"""Device programs: the selection programs' share of the HBM roofline, as
``agg_hbm_pct`` reckons the fused aggregate's. Bytes: for each query marked
``SELECT_SCAN`` whose ``execute`` spans saw the traffic's
``select_programs`` run, the rows of the tables the device selected (the
program's tally, ``summary()["selects"]["rows_in_device"]``) x the least
bytes of the columns the program reads, plus the survivors it wrote
(``rows_out_device``) x the least bytes of the columns they carry
(``peaks.MIN_BYTES``; an integer key 8). Time: the device seconds of those
runs in the trace. A lower bound on the traffic (validity planes, padding
to the bucket, the sort's passes and the i64 packing are left out), so the
share is understated, never over 100% for a program that reads what it
must. None where no selection program ran in the traced passes."""

from chipbench import peaks
from chipbench.layer_metrics import select_device_tables_pct as tables


def select_bytes(mod, rows_in: int, rows_out: int) -> int:
    """The least HBM traffic of one query's selection: each column the
    program reads, once a row; each column it writes, once a survivor."""
    scan = mod.SELECT_SCAN
    kinds = mod.SCANS[scan["table"]]
    return (rows_in * sum(peaks.MIN_BYTES[kinds[c]] for c in scan["reads"])
            + rows_out * sum(peaks.MIN_BYTES[kinds[c]]
                             for c in scan["writes"]))


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    programs = set(ctx.traffic.get("select_programs", ()))
    passes = tables.selects_of(ctx)
    if not programs or passes is None:
        return None
    order = list(ctx.traffic["queries"])
    nbytes = seconds = 0.0
    for q, mod in ctx.queries.items():
        if not hasattr(mod, "SELECT_SCAN"):
            continue
        ran = sum(s for name, s in ctx.trace.span_module_s.get(
            f"execute:{q}", {}).items() if name in programs)
        if not ran:
            continue
        seconds += ran
        for inside in passes:
            # a pass's summaries are its queries', in the traffic's order
            if len(inside) != len(order):
                continue
            sel = inside[order.index(q)]
            if sel:
                nbytes += select_bytes(mod, sel.get("rows_in_device", 0),
                                       sel.get("rows_out_device", 0))
    if not seconds or not nbytes:   # no program ran, or nothing tallies
        return None
    return 100.0 * (nbytes / ctx.peaks["hbm_bps"]) / seconds
