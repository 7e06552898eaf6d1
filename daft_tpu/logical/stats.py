"""Cardinality / size estimation over logical plans.

The reference propagates ``ApproxStats`` bottom-up (EnrichWithStats,
``src/daft-logical-plan/src/stats.rs``) to drive join reordering and
broadcast decisions. This is the same idea with simpler per-op rules: scan
stats come from parquet metadata via materialized scan tasks (cached on the
Source node); everything else applies selectivity heuristics. Estimates are
deliberately coarse — they only need to rank join orders and pick broadcast
sides, not be exact.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from . import plan as lp

# default selectivities (the reference hardcodes similar factors in its
# ApproxStats arms)
FILTER_SELECTIVITY = 0.2
EQ_FILTER_SELECTIVITY = 0.05
AGG_GROUP_FACTOR = 0.1


@dataclass(frozen=True)
class Stats:
    rows: Optional[float]
    size_bytes: Optional[float]

    def scaled(self, f: float) -> "Stats":
        return Stats(None if self.rows is None else max(self.rows * f, 1.0),
                     None if self.size_bytes is None
                     else max(self.size_bytes * f, 1.0))


UNKNOWN = Stats(None, None)


def _source_stats(node: lp.Source) -> Stats:
    if node.partitions is not None:
        try:
            parts = node.partitions
            # SpillBuffer-backed sources (AQE actuals) track counts at
            # append time — summing would reload spilled entries from disk
            rows = getattr(parts, "total_rows", None)
            size = getattr(parts, "total_bytes", None)
            if rows is None:
                rows = sum(len(p) for p in parts)
            if size is None:
                size = sum(p.size_bytes() or 0 for p in parts)
            return Stats(float(rows), float(size) or None)
        except Exception:
            return UNKNOWN
    tasks = getattr(node, "materialized_tasks", None)
    if tasks is None and node.scan_op is not None:
        try:
            tasks = node.scan_op.to_scan_tasks(node.pushdowns)
            node.materialized_tasks = tasks
        except Exception:
            return UNKNOWN
    if not tasks:
        return Stats(0.0, 0.0)
    rows = 0.0
    size = 0.0
    rows_known = True
    for t in tasks:
        r = t.num_rows()
        if r is None:
            rows_known = False
        else:
            rows += r
        size += t.size_bytes() or 0
    if not rows_known:
        # filters pushed into the scan hide exact counts: estimate from
        # bytes at ~100 B/row, times the filter selectivity
        est = (size / 100.0) * FILTER_SELECTIVITY if size else None
        return Stats(est, size * FILTER_SELECTIVITY if size else None)
    if node.pushdowns.limit is not None:
        rows = min(rows, node.pushdowns.limit)
    return Stats(rows, size or None)


def _filter_selectivity(pred) -> float:
    # an equality against a literal is much more selective than a range
    ops = set()

    def walk(e):
        ops.add(e.op)
        for c in e.args:
            walk(c)

    walk(pred)
    if "eq" in ops and not ({"or"} & ops):
        return EQ_FILTER_SELECTIVITY
    return FILTER_SELECTIVITY


def estimate(node: lp.LogicalPlan) -> Stats:
    """Bottom-up estimated (rows, bytes) for a plan subtree."""
    if isinstance(node, lp.Source):
        return _source_stats(node)
    kids = [estimate(c) for c in node.children]
    if isinstance(node, lp.Filter):
        return kids[0].scaled(_filter_selectivity(node.predicate))
    if isinstance(node, lp.Limit):
        s = kids[0]
        rows = node.limit if s.rows is None else min(s.rows, node.limit)
        return Stats(float(rows), s.size_bytes)
    if isinstance(node, lp.Sample):
        if node.fraction is not None:
            return kids[0].scaled(node.fraction)
        return Stats(float(node.size), None)
    if isinstance(node, lp.Aggregate):
        if not node.group_by:
            return Stats(1.0, 256.0)
        return kids[0].scaled(AGG_GROUP_FACTOR)
    if isinstance(node, lp.Distinct):
        return kids[0].scaled(AGG_GROUP_FACTOR)
    if isinstance(node, lp.Explode):
        return kids[0].scaled(4.0)
    if isinstance(node, lp.Concat):
        l, r = kids
        rows = None if l.rows is None or r.rows is None else l.rows + r.rows
        size = None if l.size_bytes is None or r.size_bytes is None \
            else l.size_bytes + r.size_bytes
        return Stats(rows, size)
    if isinstance(node, lp.Join):
        l, r = kids
        if node.how == "cross":
            if l.rows is None or r.rows is None:
                return UNKNOWN
            return Stats(l.rows * r.rows,
                         None if l.size_bytes is None or r.size_bytes is None
                         else l.size_bytes * max(r.rows, 1.0)
                         + r.size_bytes * max(l.rows, 1.0))
        if node.how in ("semi", "anti"):
            return l.scaled(0.5)
        if node.how == "left":
            return l
        if node.how == "right":
            return r
        # inner equi-join: PK-FK assumption — output ≈ the larger (fact)
        # side (reference stats.rs uses max-side heuristics similarly)
        if l.rows is None or r.rows is None:
            return UNKNOWN
        rows = max(l.rows, r.rows)
        size = None
        if l.size_bytes is not None and r.size_bytes is not None:
            lw = l.size_bytes / max(l.rows, 1.0)
            rw = r.size_bytes / max(r.rows, 1.0)
            size = rows * (lw + rw)
        return Stats(rows, size)
    # row-preserving ops (Project/Sort/Repartition/Window/…)
    if kids:
        return kids[0]
    return UNKNOWN


# ------------------------------------------------------------------ NDV

def column_ndv(node: lp.LogicalPlan, name: str,
               est_rows: Optional[float] = None) -> Optional[float]:
    """Approximate distinct-value count of a column in a plan subtree.

    Integer/date key columns get ``max - min + 1`` from parquet footer
    statistics (exact for the dense surrogate keys join graphs are built
    on: nationkey 0–24 → 25), capped by the subtree's estimated rows —
    a filter that keeps 1 row caps the key's ndv at 1. Columns without
    usable stats fall back to the row estimate (near-unique assumption,
    i.e. FK-join-shaped). The reference reads the same footer stats for
    its scan stats (``daft-scan``'s parquet metadata path).

    ``est_rows``: the caller's row estimate for ``node``, if already
    computed (avoids a redundant estimate() walk)."""
    est = estimate(node).rows if est_rows is None else est_rows
    footer = column_ndv_footer(node, name, est_rows=est)
    return est if footer is None else footer


def column_ndv_footer(node: lp.LogicalPlan, name: str,
                      est_rows: Optional[float] = None) -> Optional[float]:
    """Like :func:`column_ndv` but returns None instead of the near-unique
    row-estimate fallback: only parquet-footer min/max evidence counts.
    For decline-if-huge decisions (the fused-agg cardinality gate) the
    fallback would misfire — a large in-memory groupby on a 5-value key
    has no footer stats and must keep the default path."""
    src = _find_source_with(node, name)
    if src is None:
        return None
    rng = _source_column_range(src, name)
    if rng is None:
        return None
    est = estimate(node).rows if est_rows is None else est_rows
    return rng if est is None else min(rng, est)


def _find_source_with(node: lp.LogicalPlan, name: str):
    if isinstance(node, lp.Source):
        return node if name in node.schema().column_names else None
    for c in node.children:
        try:
            if name in c.schema().column_names:
                return _find_source_with(c, name)
        except Exception:
            return None
    return None


def _source_column_range(node: lp.Source, name: str) -> Optional[float]:
    """(max-min+1) over all files' footer stats for an int/date column."""
    cache = getattr(node, "_ndv_cache", None)
    if cache is None:
        cache = node._ndv_cache = {}
    if name in cache:
        return cache[name]
    out = None
    try:
        tasks = getattr(node, "materialized_tasks", None)
        if tasks is None and node.scan_op is not None:
            tasks = node.scan_op.to_scan_tasks(node.pushdowns)
            node.materialized_tasks = tasks
        lo = hi = None
        seen_paths = set()
        if tasks:
            from ..io import footers
            for t in tasks:
                if t.file_format != "parquet":
                    raise ValueError
                # split tasks share a file; one footer per path, reusing
                # the footer the task was planned from when it carries one
                md_cached = getattr(t, "pq_metadata", None)
                for k, p in enumerate(t.paths):
                    if p in seen_paths:
                        continue
                    seen_paths.add(p)
                    md = md_cached if md_cached is not None \
                        and len(t.paths) == 1 \
                        else footers.footer(p, t.io_config,
                                            t.identity(k)).metadata
                    idx = {md.schema.column(i).name: i
                           for i in range(md.num_columns)}.get(name)
                    if idx is None:
                        raise ValueError
                    for rg in range(md.num_row_groups):
                        st = md.row_group(rg).column(idx).statistics
                        if st is None or not st.has_min_max:
                            raise ValueError
                        mn, mx = st.min, st.max
                        if not isinstance(mn, int) or isinstance(mn, bool):
                            import datetime as _dt
                            # date is day-granular so max-min+1 is an ndv
                            # bound; datetime is NOT (a day of distinct
                            # timestamps would collapse to ndv 1) — reject
                            if isinstance(mn, _dt.datetime) \
                                    or not isinstance(mn, _dt.date):
                                raise ValueError
                            mn, mx = mn.toordinal(), mx.toordinal()
                        lo = mn if lo is None else min(lo, mn)
                        hi = mx if hi is None else max(hi, mx)
        if lo is not None:
            out = float(hi - lo + 1)
    except Exception:
        out = None
    cache[name] = out
    return out
