"""Host operators: of the rows the traced passes' joins were handed, the
share they gave back: matched output rows over rows in
(``summary()["joins"]``: ``rows_out`` / (``rows_host`` + ``rows_device``)),
over all traced passes together. Under 1 where a join throws away almost
everything it is handed, which is the share a filter made of the join's
key set and pushed into the scan cannot beat (Q17: 0.1% of ``lineitem``
has a part of the brand and container asked for). None when the program
does not tally ``rows_out`` (the parent of PR 48), or no traced pass
matched a pair."""

from chipbench.layer_metrics import join_device_pairs_pct


def read(ctx):
    joins = join_device_pairs_pct.joins_of(ctx)
    if not joins or not any("rows_out" in j for j in joins):
        return None
    rows_in = sum(j.get("rows_host", 0) + j.get("rows_device", 0)
                  for j in joins)
    if not rows_in:
        return None
    return 100.0 * sum(j.get("rows_out", 0) for j in joins) / rows_in
