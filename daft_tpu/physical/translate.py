"""Logical → physical translation.

Reference: ``src/daft-local-plan/src/translate.rs:19-434`` (direct lowering,
Aggregate → partial/final split) and
``src/daft-physical-plan/src/physical_planner/translate.rs:639,914``
(``populate_aggregation_stages``, shuffle insertion, broadcast-join decision
by size threshold).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from ..context import get_context
from ..datatype import DataType
from ..expressions import Expression, col, lit
from ..logical import plan as lp
from ..logical import stats as lstats
from ..schema import Schema
from . import plan as pp

# aggs outside the decomposition table cannot be split into partial/final
# stages → single-stage agg (single-sourced with the pipeline reducer and
# the distributed map-side combine: ``aggs.AGG_DECOMPOSITION`` is the
# decomposition table of record)
from ..aggs import AGG_DECOMPOSITION as _DECOMPOSABLE


import threading as _threading

_tl = _threading.local()


def translate(plan: lp.LogicalPlan) -> pp.PhysicalPlan:
    """Logical → physical, deduplicating SHARED subplans: logically equal
    subtrees (by ``semantic_id``) map to one physical node whose
    ``shared_consumers`` counts its parents — the executor materializes it
    once and streams the buffer to every consumer (TPC-H Q21's ``base``
    chain and late-lineitem dedup otherwise execute 2-3× each)."""
    cfg = get_context().execution_config
    fresh = not getattr(_tl, "active", False)
    if fresh:
        _tl.active = True
        _tl.memo = {}
    try:
        out = _t(plan, cfg)
        if fresh:
            # round 21: grow maximal device-eligible operator chains into
            # FusedRegion nodes (whole-query compilation) — outermost call
            # only, so nested stage translations rewrite exactly once
            from . import fusion
            out = fusion.fuse_regions(out, cfg)
        return out
    finally:
        if fresh:
            _tl.active = False
            _tl.memo = {}


def repeated_scans(plan: pp.PhysicalPlan) -> int:
    """The scans of a physical plan that read a file an earlier scan of
    the same plan reads (a scan shared by several consumers is one
    scan): what a common-subplan rule or a wider column set would save.
    ``summary()["plan"]["repeated_scans"]``."""
    seen: set = set()
    visited: set = set()
    repeated = 0
    stack = [plan]
    while stack:
        node = stack.pop()
        if id(node) in visited:
            continue
        visited.add(id(node))
        if isinstance(node, pp.ScanSource):
            paths = {p for t in node.tasks for p in t.paths}
            repeated += bool(paths & seen)
            seen |= paths
        stack.extend(reversed(node.children))
    return repeated


def _nondeterministic(node: lp.LogicalPlan) -> bool:
    """True when the subtree's output is not a pure function of its
    inputs — e.g. an unseeded Sample. Such subtrees must never merge:
    two identical .sample() calls are independent draws."""
    if isinstance(node, lp.Sample) and node.seed is None:
        return True
    return any(_nondeterministic(c) for c in node.children)


def _t(node: lp.LogicalPlan, cfg) -> pp.PhysicalPlan:
    if getattr(_tl, "active", False):
        key = node.semantic_id()
        hit = _tl.memo.get(key)
        if hit is not None:
            hit.shared_consumers = getattr(hit, "shared_consumers", 1) + 1
            return hit
        out = _t_node(node, cfg)
        if not _nondeterministic(node):
            _tl.memo[key] = out
        return out
    return _t_node(node, cfg)


def _t_node(node: lp.LogicalPlan, cfg) -> pp.PhysicalPlan:
    if isinstance(node, lp.Source):
        if node.partitions is not None:
            return pp.InMemorySource(node.partitions, node.schema())
        tasks = getattr(node, "materialized_tasks", None)
        if tasks is None:
            tasks = node.scan_op.to_scan_tasks(node.pushdowns)
        return pp.ScanSource(tasks, node.schema())
    if isinstance(node, lp.Project):
        return pp.Project(_t(node.children[0], cfg), node.exprs, node.schema())
    if isinstance(node, lp.UDFProject):
        return pp.UDFProject(_t(node.children[0], cfg), node.exprs,
                             node.schema(), node.concurrency)
    if isinstance(node, lp.Filter):
        return pp.Filter(_t(node.children[0], cfg), node.predicate)
    if isinstance(node, lp.Limit):
        return pp.Limit(_t(node.children[0], cfg), node.limit, node.offset)
    if isinstance(node, lp.Explode):
        return pp.Explode(_t(node.children[0], cfg), node.exprs, node.schema())
    if isinstance(node, lp.Unpivot):
        return pp.Unpivot(_t(node.children[0], cfg), node.ids, node.values,
                          node.variable_name, node.value_name, node.schema())
    if isinstance(node, lp.Sample):
        return pp.Sample(_t(node.children[0], cfg), node.fraction, node.size,
                         node.with_replacement, node.seed)
    if isinstance(node, lp.MonotonicallyIncreasingId):
        return pp.MonotonicallyIncreasingId(_t(node.children[0], cfg),
                                            node.column_name, node.schema())
    if isinstance(node, lp.Sort):
        return pp.Sort(_t(node.children[0], cfg), node.sort_by,
                       node.descending, node.nulls_first)
    if isinstance(node, lp.TopN):
        return pp.TopN(_t(node.children[0], cfg), node.sort_by,
                       node.descending, node.nulls_first, node.limit)
    if isinstance(node, lp.Repartition):
        child = _t(node.children[0], cfg)
        spec = node.spec
        kind = {"hash": "hash", "random": "random", "range": "range",
                "unknown": "split"}[spec.kind]
        return pp.Exchange(child, kind, spec.num_partitions, spec.by,
                           spec.descending)
    if isinstance(node, lp.Distinct):
        child = _t(node.children[0], cfg)
        on = node.on or [col(n) for n in node.schema().column_names]
        ex = pp.Exchange(child, "hash", max(_nparts(node.children[0]), 1),
                         tuple(on), engine_inserted=True)
        return pp.Dedup(ex, on)
    if isinstance(node, lp.Aggregate):
        return _translate_agg(node, cfg)
    if isinstance(node, lp.Pivot):
        child = _t(node.children[0], cfg)
        gather = pp.Exchange(child, "gather", 1)
        return pp.Pivot(gather, node.group_by, node.pivot_col, node.value_col,
                        node.names, node.schema())
    if isinstance(node, lp.Window):
        child = _t(node.children[0], cfg)
        if node.partition_by:
            child = pp.Exchange(child, "hash", _nparts(node.children[0]),
                                tuple(node.partition_by))
        else:
            child = pp.Exchange(child, "gather", 1)
        return pp.Window(child, node.window_exprs, node.partition_by,
                         node.order_by, node.descending, node.nulls_first,
                         node.frame, node.schema())
    if isinstance(node, lp.Concat):
        return pp.Concat(_t(node.children[0], cfg), _t(node.children[1], cfg))
    if isinstance(node, lp.Join):
        return _translate_join(node, cfg)
    if isinstance(node, lp.Sink):
        child = _t(node.children[0], cfg)
        return pp.Write(child, node.info, node.schema())
    raise NotImplementedError(f"translate for {node.name()}")


def _nparts(node: lp.LogicalPlan) -> int:
    return max(node.num_partitions(), 1)


def _estimate_size(node: lp.LogicalPlan) -> Optional[int]:
    """Best-effort size estimate for join-strategy choice."""
    if isinstance(node, lp.Source):
        if node.partitions is not None:
            try:
                sz = getattr(node.partitions, "total_bytes", None)
                if sz is not None:
                    return sz
                return sum(p.size_bytes() or 0 for p in node.partitions)
            except Exception:
                return None
        tasks = getattr(node, "materialized_tasks", None)
        if tasks is None and node.scan_op is not None:
            tasks = node.scan_op.to_scan_tasks(node.pushdowns)
            node.materialized_tasks = tasks
        if tasks is not None:
            sizes = [t.size_bytes() for t in tasks]
            if all(s is not None for s in sizes):
                return sum(sizes)
        return None
    if isinstance(node, (lp.Filter, lp.Sample)):
        base = _estimate_size(node.children[0])
        return None if base is None else int(base * 0.2)
    if isinstance(node, lp.Limit):
        return 1024 * node.limit  # rough
    if isinstance(node, lp.Aggregate):
        base = _estimate_size(node.children[0])
        return None if base is None else max(int(base * 0.05), 1024)
    if isinstance(node, lp.Distinct):
        # DISTINCT on key columns often barely reduces (TPC-H Q21's
        # (orderkey, suppkey) pairs: 6M → 6M rows); pricing it like an
        # aggregation mispredicted a 100MB build side as broadcastable
        base = _estimate_size(node.children[0])
        return None if base is None else max(int(base * 0.5), 1024)
    if node.children:
        sizes = [_estimate_size(c) for c in node.children]
        if any(s is None for s in sizes):
            return None
        return sum(sizes)
    return None


def _translate_join(node: lp.Join, cfg) -> pp.PhysicalPlan:
    left, right = node.children
    pl, pr = _t(left, cfg), _t(right, cfg)
    if node.how == "cross":
        gather_r = pp.Exchange(pr, "gather", 1)
        return pp.CrossJoin(pl, gather_r, node.schema())
    lsize, rsize = _estimate_size(left), _estimate_size(right)
    threshold = cfg.broadcast_join_size_bytes_threshold
    strategy = node.strategy
    if strategy is None:
        if (rsize is not None and rsize <= threshold
                and node.how in ("inner", "left", "semi", "anti")):
            strategy = "broadcast_right"
        elif (lsize is not None and lsize <= threshold
              and node.how in ("inner", "right")):
            strategy = "broadcast_left"
        else:
            strategy = "hash"
    elif strategy == "broadcast":
        strategy = "broadcast_right" if node.how in ("inner", "left", "semi",
                                                     "anti") else "hash"
    if strategy == "sort_merge":
        # no exchanges here: the executor samples both sides and range-
        # partitions them with one shared boundary set (aligned-boundary
        # sort-merge, reference SortMergeJoin)
        return pp.HashJoin(pl, pr, node.left_on, node.right_on, node.how,
                           node.schema(), "sort_merge")
    if strategy == "hash" and (_nparts(left) > 1 or _nparts(right) > 1):
        n = max(_nparts(left), _nparts(right))
        # join-side exchanges are NOT count-adaptable (the two sides must
        # keep identical partition counts), but they ARE strategy-adaptable:
        # the executor's AQE path may demote the pair to a broadcast join
        # from measured sizes (reference: AdaptivePlanner re-planning joins
        # from materialized stats, planner.rs:451-640) — join_side marks
        # them as elidable.
        pl = pp.Exchange(pl, "hash", n, tuple(node.left_on))
        pr = pp.Exchange(pr, "hash", n, tuple(node.right_on))
        pl.join_side = True
        pr.join_side = True
    elif strategy == "broadcast_right":
        pr = pp.Exchange(pr, "gather", 1)
    elif strategy == "broadcast_left":
        pl = pp.Exchange(pl, "gather", 1)
    join = pp.HashJoin(pl, pr, node.left_on, node.right_on, node.how,
                       node.schema(), strategy)
    # footer-backed size evidence for the grace hash join's first-level
    # radix fanout (execution/out_of_core.plan_partitions): enough
    # buckets that each is EXPECTED to fit the pair budget — recursion
    # is the safety net when the estimate is wrong, not the plan
    join.left_bytes_est = lsize
    join.right_bytes_est = rsize
    return join


def _translate_agg(node: lp.Aggregate, cfg) -> pp.PhysicalPlan:
    from ..aggs import split_agg_expr
    child = node.children[0]
    pchild = _t(child, cfg)
    nparts = _nparts(child)
    specs = [split_agg_expr(e) for e in node.aggs]
    decomposable = all(op in _DECOMPOSABLE for op, _, _, _ in specs)

    if not decomposable:
        # gather everything and aggregate once
        if node.group_by:
            ex = pp.Exchange(pchild, "hash",
                             min(nparts, cfg.shuffle_aggregation_default_partitions),
                             tuple(node.group_by), engine_inserted=True)
        else:
            ex = pp.Exchange(pchild, "gather", 1)
        return pp.Aggregate(ex, node.aggs, node.group_by, node.schema(),
                            "single")

    partial_aggs, final_aggs, final_proj = _split_aggs(node, child.schema())
    p1_schema = _agg_schema(node.group_by, partial_aggs, child.schema())
    p1 = _try_fuse_partial(pchild, partial_aggs, node.group_by, p1_schema)
    if p1 is None:
        p1 = pp.Aggregate(pchild, partial_aggs, node.group_by, p1_schema,
                          "partial")
    gb2 = [col(e.name()) for e in node.group_by]
    f_schema = _agg_schema(gb2, final_aggs, p1_schema)
    est_rows = lstats.estimate(child).rows
    mesh_ex = _try_mesh_exchange_agg(p1, final_aggs, gb2, f_schema,
                                     p1_schema, est_rows)
    if mesh_ex is not None:
        p2 = mesh_ex
    else:
        if node.group_by:
            ex = pp.Exchange(
                p1, "hash",
                min(max(nparts, 1), cfg.shuffle_aggregation_default_partitions)
                if nparts > 1 else 1,
                tuple(col(e.name()) for e in node.group_by),
                engine_inserted=True)
        else:
            ex = pp.Exchange(p1, "gather", 1)
        p2 = pp.Aggregate(ex, final_aggs, gb2, f_schema, "final")
        # footer-backed output-cardinality estimate for the executor's
        # fused-dispatcher gate (max over keys is a lower bound on the
        # grouped output; enough for a decline-if-huge decision). The raw
        # row estimate rides along as the gate's fallback evidence: with
        # no footer stats (in-memory/CSV sources) it is an upper bound on
        # the group count, which is exactly what decline-if-huge needs.
        ndvs = [v for v in (lstats.column_ndv_footer(child, e.name(),
                                                     est_rows=est_rows)
                            for e in node.group_by) if v is not None]
        p2.group_ndv = max(ndvs) if ndvs else None
        p2.group_rows_est = est_rows
    proj = [col(e.name()) for e in node.group_by] + final_proj
    return pp.Project(p2, proj, node.schema())


def _try_mesh_exchange_agg(p1, final_aggs, gb2, f_schema: Schema,
                           p1_schema: Schema,
                           est_rows=None) -> Optional[pp.PhysicalPlan]:
    """Choose the ICI-collective shuffle+merge when statically sound: a
    multi-device mesh is up, the input is big enough to repay the
    collective program, every group key / partial value either
    round-trips the device encoding bit-exactly or is string/binary (those
    ride shared-dictionary codes — see ``_exchangeable``), and every final
    op merges with itself."""
    from ..aggs import split_agg_expr
    from ..device import column as dcol, runtime as drt
    from ..parallel import mesh as pmesh
    from ..parallel.exchange import MERGEABLE_OPS
    if not gb2:
        return None  # global aggs gather a handful of scalars — host wins
    if not drt.device_enabled() or pmesh.mesh_size() < 2:
        return None
    # admission is priced, not thresholded: the cost model compares the
    # collective (dispatch + bytes over the calibrated ICI rate) against
    # a host exchange pass over the estimated bytes; DAFT_TPU_MESH_MIN_ROWS
    # (when set) force-overrides with the old static row floor
    row_bytes = 8.0 * max(len(gb2) + len(final_aggs), 1)
    if not pmesh.mesh_admits(est_rows, row_bytes):
        return None
    def _exchangeable(dtype) -> bool:
        # bit-exact round trip, or string/binary riding shared dictionary
        # codes (the executor concats all partitions into one batch before
        # encoding, so every shard shares one sorted dictionary — codes are
        # comparable AND lexicographically ordered; see _np_plane_encoder)
        return (dcol.is_lossless_device_dtype(dtype)
                or dtype.is_string() or dtype.is_binary())

    for g in gb2:
        if not _exchangeable(p1_schema[g.name()].dtype):
            return None
    for a in final_aggs:
        op, child_e, name, params = split_agg_expr(a)
        if op not in MERGEABLE_OPS:
            return None
        if child_e is None or child_e._unalias().op != "col":
            return None
        if not _exchangeable(p1_schema[child_e._unalias().params[0]].dtype):
            return None
    return pp.DeviceExchangeAgg(p1, final_aggs, gb2, f_schema)


def _try_fuse_partial(pchild: pp.PhysicalPlan, partial_aggs, group_by,
                      p1_schema: Schema) -> Optional[pp.PhysicalPlan]:
    """Collapse partial-Agg ← Project* ← Filter* ← Scan into a fused device
    fragment, substituting intermediate projections so every expression is
    over source columns."""
    from ..aggs import split_agg_expr
    from ..logical.optimizer import combine_conjuncts, substitute_columns
    chain = []
    n = pchild
    while isinstance(n, (pp.Project, pp.Filter)):
        chain.append(n)
        n = n.children[0]
    # chain may be empty: fusing projection-exprs + agg over a bare source
    # still collapses to one program (scan-level filters prune earlier)
    if not isinstance(n, (pp.ScanSource, pp.InMemorySource)):
        return None
    mapping = {c: col(c) for c in n.schema().column_names}
    preds = []
    for node2 in reversed(chain):
        if isinstance(node2, pp.Filter):
            preds.append(substitute_columns(node2.predicate, mapping))
        else:
            try:
                mapping = {e.name(): substitute_columns(e._unalias(), mapping)
                           for e in node2.exprs}
            except Exception:
                return None
    try:
        gb2 = [substitute_columns(e._unalias(), mapping).alias(e.name())
               for e in group_by]
        aggs2 = []
        for a in partial_aggs:
            op, child, name, params = split_agg_expr(a)
            if op not in ("sum", "mean", "min", "max", "count", "stddev",
                          "var", "any_value", "bool_and", "bool_or"):
                return None
            if op == "count" and params and params[0] != "valid":
                return None
            c2 = substitute_columns(child, mapping) if child is not None \
                else None
            new_inner = Expression("agg." + op,
                                   (c2,) if c2 is not None else (), params)
            aggs2.append(new_inner.alias(name))
        pred = combine_conjuncts(preds) if preds else None
        # all agg outputs must be decodable without a dictionary
        for a in aggs2:
            f = p1_schema[a.name()]
            if f.dtype.device_repr() is None or f.dtype.is_string() \
                    or f.dtype.is_binary():
                return None
        # string group keys must be source-column passthroughs (their
        # dictionary travels from the encoded input)
        for g in gb2:
            f = p1_schema[g.name()]
            if (f.dtype.is_string() or f.dtype.is_binary()) \
                    and g._unalias().op != "col":
                return None
    except Exception:
        return None
    return pp.DeviceFragmentAgg(n, pred, aggs2, gb2, p1_schema, "partial")


def _agg_schema(group_by, aggs, input_schema: Schema) -> Schema:
    fields = [e.to_field(input_schema) for e in group_by]
    fields += [e.to_field(input_schema) for e in aggs]
    return Schema(fields)


def _split_aggs(node: lp.Aggregate, in_schema: Schema):
    """populate_aggregation_stages: per-agg partial exprs, final exprs over
    partial outputs, and the final projection."""
    partials: List[Expression] = []
    finals: List[Expression] = []
    projs: List[Expression] = []
    seen_partial = {}

    def add_partial(e: Expression) -> str:
        k = e._key()
        if k in seen_partial:
            return seen_partial[k]
        nm = e.name() if e.op == "alias" else f"__p{len(partials)}__{e.name()}"
        seen_partial[k] = nm
        partials.append(e.alias(nm) if e.name() != nm else e)
        return nm

    for e in node.aggs:
        out_name = e.name()
        inner = e._unalias()
        op = inner.op[4:]
        child = inner.args[0] if inner.args else None
        out_field = e.to_field(in_schema)
        if op in ("sum", "min", "max", "any_value", "bool_and", "bool_or",
                  "list", "concat"):
            p = add_partial(Expression(inner.op, inner.args, inner.params)
                            .alias(out_name))
            f_op = {"sum": "agg.sum", "min": "agg.min", "max": "agg.max",
                    "any_value": "agg.any_value", "bool_and": "agg.bool_and",
                    "bool_or": "agg.bool_or", "list": "agg.concat",
                    "concat": "agg.concat"}[op]
            finals.append(Expression(f_op, (col(p),),
                                     inner.params).alias(out_name))
            projs.append(col(out_name))
        elif op == "count":
            p = add_partial(inner.alias(out_name))
            finals.append(col(p).sum().alias(out_name))
            projs.append(col(out_name).cast(DataType.uint64()).alias(out_name))
        elif op == "mean":
            s = add_partial(child.sum().alias(f"__sum_{out_name}__"))
            c = add_partial(child.count().alias(f"__count_{out_name}__"))
            fs = f"__fsum_{out_name}__"
            fc = f"__fcount_{out_name}__"
            finals.append(col(s).sum().alias(fs))
            finals.append(col(c).sum().alias(fc))
            projs.append((col(fs).cast(DataType.float64())
                          / col(fc).cast(DataType.float64())).alias(out_name))
        elif op in ("stddev", "var"):
            s = add_partial(child.sum().alias(f"__sum_{out_name}__"))
            c = add_partial(child.count().alias(f"__count_{out_name}__"))
            s2 = add_partial((child * child).sum().alias(f"__sumsq_{out_name}__"))
            fs, fc, fs2 = (f"__fs_{out_name}__", f"__fc_{out_name}__",
                           f"__fs2_{out_name}__")
            finals.append(col(s).sum().alias(fs))
            finals.append(col(c).sum().alias(fc))
            finals.append(col(s2).sum().alias(fs2))
            mean = col(fs).cast(DataType.float64()) / col(fc).cast(DataType.float64())
            var = (col(fs2).cast(DataType.float64())
                   / col(fc).cast(DataType.float64())) - mean * mean
            projs.append((var.sqrt() if op == "stddev" else var).alias(out_name))
        else:
            raise NotImplementedError(f"agg split for {op}")
    return partials, finals, projs
