"""Partial decode: how many tables' packed results the program decoded into
each record batch, over the traced passes (the program's tally on each
query's trace, ``decode``: ``tables`` and ``batches``). 1 where every table
is decoded alone, whatever its rows; the tables of a fetch window where a
window is decoded lane by lane as one batch. None when the program tallies
neither (the parent of PR 37), or no traced pass decoded a device result."""

from chipbench import program_spans


def read(ctx):
    summaries = program_spans.finished()
    traced = program_spans.by_pass(ctx.passes, summaries) if summaries \
        else []
    tables = batches = 0
    for inside in traced:
        for s in inside:
            tables += s.get("decode", {}).get("tables", 0)
            batches += s.get("decode", {}).get("batches", 0)
    return tables / batches if batches else None
