"""Host operators: the largest bucket pair the traced passes' joins
matched, rows of the left side + rows of the right, in millions: the size
the join gate's break-even (``costmodel.join_wins``: host rows/s against
upload + program + download) is compared with. The program keeps it on each
query's trace (``summary()["joins"]["max_pair_rows"]``; the same numbers
are ``rows_left`` / ``rows_right`` on the pair's ``join:build`` or
``join:device`` span, which a summary does not carry one by one). It does
not grow with the fan-out, only with the data: ~0.21 at SF1, ~2.1 at SF10.
None when the program keeps none (the parent of PR 38), or no traced pass
matched a pair."""

from chipbench.layer_metrics import join_device_pairs_pct


def read(ctx):
    joins = join_device_pairs_pct.joins_of(ctx)
    if not joins:
        return None
    most = max(j.get("max_pair_rows", 0) for j in joins)
    return most / 1e6 if most else None
