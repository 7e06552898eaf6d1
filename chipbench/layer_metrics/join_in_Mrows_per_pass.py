"""Host operators: the rows a pass's joins were handed, in millions: left
+ right of every bucket pair matched, on the host or on the device
(``summary()["joins"]``: ``rows_host`` + ``rows_device``), added over a
pass's queries, median over the traced passes. A side matched against
many morsels (a broadcast build side) counts once a pair. Beside
``join_out_pct`` it says what a join's key set would have saved the scan,
the exchange and the probe. None when the program tallies neither (the
parent of PR 38), or no traced pass holds a summary."""

import statistics

from chipbench import program_spans


def tallies_by_pass(ctx, group):
    """For each traced pass the ``group`` tallies of its queries'
    summaries added key by key, one dict a pass; a pass none of whose
    summaries has the group is left out. None when no pass holds a
    summary."""
    summaries = program_spans.finished()
    traced = program_spans.by_pass(ctx.passes, summaries) if summaries \
        else []
    if not traced:
        return None
    out = []
    for inside in traced:
        held = [s[group] for s in inside if group in s]
        if held:
            keys = {k for t in held for k in t}
            out.append({k: sum(t.get(k, 0) for t in held) for k in keys})
    return out


def read(ctx):
    passes = tallies_by_pass(ctx, "joins")
    if not passes:
        return None
    return statistics.median(
        p.get("rows_host", 0) + p.get("rows_device", 0)
        for p in passes) / 1e6
