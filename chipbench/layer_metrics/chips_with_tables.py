"""Device memory: how many chips served at least one table to a traced
pass's device programs (the program's per-chip tally on each query's
trace, ``chips``: a table is counted under the chip that holds it and
runs its program), median over the traced passes. 1 where one chip is
visible; on a four-chip host it has to read 4, or chips sit idle."""

import statistics

from chipbench import program_spans


def per_chip(ctx):
    """For each traced pass ``{chip: {"tables", "rows",
    "resident_bytes"}}``: tables and rows added over the pass's queries,
    resident bytes the largest any of them noted. None when the program
    keeps no summaries, none lies in a pass, or they carry no ``chips``
    (a program that places nothing)."""
    summaries = program_spans.finished()
    traced = program_spans.by_pass(ctx.passes, summaries) if summaries \
        else []
    if not any("chips" in s for inside in traced for s in inside):
        return None
    out = []
    for inside in traced:
        chips = {}
        for s in inside:
            for c in s.get("chips", ()):
                at = chips.setdefault(c["chip"], {
                    "tables": 0, "rows": 0, "resident_bytes": 0})
                at["tables"] += c.get("tables", 0)
                at["rows"] += c.get("rows", 0)
                at["resident_bytes"] = max(at["resident_bytes"],
                                           c.get("resident_bytes", 0))
        out.append(chips)
    return out


def read(ctx):
    passes = per_chip(ctx)
    if passes is None:
        return None
    return statistics.median(
        sum(1 for c in chips.values() if c["tables"]) for chips in passes)
