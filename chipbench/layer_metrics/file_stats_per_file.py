"""API / plan: stat-like system calls the program made on its scans' files
inside the traced passes' queries, over the files those scans made tasks
for (the program's tally on each query's trace, ``files``: ``stats`` and
``planned``). 1 where every local file a query reads is stat-ed once, when
its scan's tasks are made, and that identity serves the footer store and
the HBM cache's fingerprint alike; 3 where the planner stats a file and
the executor looks at it twice more. Calls made outside a query (the
listing and the schema inference of the query builders, which ``plan_ms``
times) are on no trace and not counted. None when the program tallies
neither, or no traced pass planned a file."""

from chipbench import program_spans


def read(ctx):
    summaries = program_spans.finished()
    traced = program_spans.by_pass(ctx.passes, summaries) if summaries \
        else []
    stats = planned = 0
    for inside in traced:
        for s in inside:
            stats += s.get("files", {}).get("stats", 0)
            planned += s.get("files", {}).get("planned", 0)
    return stats / planned if planned else None
