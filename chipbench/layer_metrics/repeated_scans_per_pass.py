"""API / plan: the scans of a pass's physical plans that read files an
earlier scan of the same plan reads (``summary()["plan"]``:
``repeated_scans``, counted once a query where the plan is built), added
over a pass's queries, median over the traced passes. 2 for Q17, whose
two joins of ``part`` to ``lineitem`` need other columns and so are
planned, scanned and probed apart; 0 for Q6. What a common-subplan rule,
or one scan with the wider column set, would save. None when the program
keeps no such tally (the parent of PR 48), or no traced pass holds a
summary."""

import statistics

from chipbench.layer_metrics import join_in_Mrows_per_pass as join_in


def read(ctx):
    passes = join_in.tallies_by_pass(ctx, "plan")
    if not passes:
        return None
    return statistics.median(p.get("repeated_scans", 0) for p in passes)
