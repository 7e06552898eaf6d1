"""Dispatch gate: of the bucket pairs the traced passes' joins matched (one
a call of ``joins.match_indices``), the share the fused device program took
(``join:device``), from the program's tally on each query's trace
(``summary()["joins"]``: ``pairs_device`` / ``pairs_host``). 0 where the
gate kept every pair on the host. None when the program tallies neither
(the parent of PR 38), or no traced pass matched a pair."""

from chipbench import program_spans


def joins_of(ctx):
    """The ``joins`` tallies of the traced passes' queries, one dict a
    query whose summary has them; None when no pass holds a summary."""
    summaries = program_spans.finished()
    traced = program_spans.by_pass(ctx.passes, summaries) if summaries \
        else []
    if not traced:
        return None
    return [s["joins"] for inside in traced for s in inside if "joins" in s]


def read(ctx):
    joins = joins_of(ctx)
    if not joins:
        return None
    device = sum(j.get("pairs_device", 0) for j in joins)
    pairs = device + sum(j.get("pairs_host", 0) for j in joins)
    return 100.0 * device / pairs if pairs else None
