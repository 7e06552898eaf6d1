"""API / plan: of the local Parquet footers the traced passes' scans were
planned from, the share the program already held (its per-file footer
store: one ``stat`` a file) against those it opened, read and parsed
(the program's tally on each query's trace, ``footers``: ``from_store``
and ``read``). 100 once the warm-up has seen every file; None when the
program tallies neither, or no traced pass planned a local Parquet file."""

from chipbench import program_spans


def read(ctx):
    summaries = program_spans.finished()
    traced = program_spans.by_pass(ctx.passes, summaries) if summaries \
        else []
    held = read_ = 0
    for inside in traced:
        for s in inside:
            held += s.get("footers", {}).get("from_store", 0)
            read_ += s.get("footers", {}).get("read", 0)
    return 100.0 * held / (held + read_) if held + read_ else None
