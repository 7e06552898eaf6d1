"""Scan planning: Pushdowns, ScanTask, ScanOperator, glob scans.

Reference: ``src/common/scan-info/src/scan_operator.rs:12-37`` (ScanOperator
trait + Pushdowns), ``src/daft-scan/src/lib.rs:417-436`` (ScanTask fields),
``src/daft-scan/src/glob.rs:28`` (GlobScanOperator with schema inference from
the first file), ``src/daft-scan/src/scan_task_iters/`` (merge-by-size 96–384MB
and split-by-rowgroup).
"""

from __future__ import annotations

import dataclasses
import fnmatch
import glob as _glob
import os
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import pyarrow as pa
import pyarrow.dataset as pads

from ..datatype import DataType
from ..expressions import Expression, col
from ..recordbatch import RecordBatch
from ..schema import Field, Schema
from . import footers


@dataclasses.dataclass(frozen=True)
class Pushdowns:
    """Pushed-down scan constraints (reference: ``pushdowns.rs``)."""

    filters: Optional[Expression] = None
    partition_filters: Optional[Expression] = None
    columns: Optional[Tuple[str, ...]] = None
    limit: Optional[int] = None

    def with_columns(self, columns: Optional[Sequence[str]]) -> "Pushdowns":
        return dataclasses.replace(
            self, columns=tuple(columns) if columns is not None else None)

    def with_limit(self, limit: Optional[int]) -> "Pushdowns":
        return dataclasses.replace(self, limit=limit)

    def with_filters(self, filters: Optional[Expression]) -> "Pushdowns":
        return dataclasses.replace(self, filters=filters)


class ScanTask:
    """One unit of scan work: file(s) + format + pushdowns.

    ``execute()`` → list[RecordBatch]; runs on the executor's IO pool.
    """

    def __init__(self, paths: List[str], file_format: str, schema: Schema,
                 pushdowns: Pushdowns = Pushdowns(),
                 num_rows_hint: Optional[int] = None,
                 size_bytes_hint: Optional[int] = None,
                 row_groups: Optional[List[Optional[List[int]]]] = None,
                 format_options: Optional[Dict[str, Any]] = None,
                 partition_values: Optional[Dict[str, Any]] = None,
                 generator: Optional[Callable[[], Iterator[RecordBatch]]] = None,
                 io_config: Any = None,
                 identities: Optional[List[Tuple[int, int]]] = None):
        self.paths = paths
        #: ``(st_size, st_mtime_ns)`` of each of ``paths`` as read when
        #: the task was made (``footers.identities``), or None for a task
        #: that carries none (a path that could not be stat-ed, a catalog
        #: reader's task): ``device/cache.task_fingerprint`` then stats
        #: for itself
        self.identities = identities
        self.io_config = io_config
        self.file_format = file_format
        self.schema = schema
        self.pushdowns = pushdowns
        self._num_rows = num_rows_hint
        self._size_bytes = size_bytes_hint
        self.row_groups = row_groups
        self.format_options = format_options or {}
        self.partition_values = partition_values or {}
        self.generator = generator

    def rows_scanned(self) -> Optional[int]:
        """The rows of the files' selected row groups, before any filter
        or limit (the footer's count; None where nothing planned it)."""
        return self._num_rows

    def unfiltered(self) -> "ScanTask":
        """This task less its pushdown filter: the same files, row groups
        and columns, every row of them (what the HBM column cache keeps
        for a selection that runs on the device)."""
        twin = ScanTask(
            self.paths, self.file_format, self.schema,
            self.pushdowns.with_filters(None), self._num_rows,
            self._size_bytes, self.row_groups, self.format_options,
            self.partition_values, self.generator, self.io_config,
            self.identities)
        md = getattr(self, "pq_metadata", None)
        if md is not None:
            twin.pq_metadata = md
        return twin

    def materialized_schema(self) -> Schema:
        if self.pushdowns.columns is not None:
            keep = [n for n in self.pushdowns.columns if n in self.schema]
            return self.schema.project(keep)
        return self.schema

    def identity(self, i: int) -> Optional[Tuple[int, int]]:
        """The identity ``paths[i]`` was planned under, or None."""
        return None if self.identities is None else self.identities[i]

    def num_rows(self) -> Optional[int]:
        if self.pushdowns.filters is not None:
            return None
        if self._num_rows is not None and self.pushdowns.limit is not None:
            return min(self._num_rows, self.pushdowns.limit)
        return self._num_rows

    def size_bytes(self) -> Optional[int]:
        return self._size_bytes

    def stream_batches(self) -> Iterator[RecordBatch]:
        """Stream result batches (one per source file) with residual
        pushdowns applied incrementally — the prefetch-pipelined scan
        yields morsels off this as each file decodes, and a satisfied
        limit stops reading the remaining files. May yield nothing for an
        all-filtered task (``execute`` adds the empty-batch fallback)."""
        from . import readers
        src = self.generator() if self.generator is not None \
            else readers.iter_scan_task_batches(self)
        remaining = self.pushdowns.limit
        for b in src:
            if self.pushdowns.filters is not None:
                b = b.filter(self.pushdowns.filters)
            if remaining is not None:
                if remaining <= 0:
                    return
                b = b.head(remaining)
                remaining -= len(b)
            if len(b):
                yield b

    def execute(self) -> List[RecordBatch]:
        out = list(self.stream_batches())
        if not out:
            return [RecordBatch.empty(self.materialized_schema())]
        return out

    def __repr__(self):
        return (f"ScanTask({self.file_format}, {len(self.paths)} files, "
                f"rows={self._num_rows}, pushdowns={self.pushdowns})")


class ScanOperator:
    """Produces ScanTasks for a source (reference trait: scan_operator.rs:12-37)."""

    def schema(self) -> Schema:
        raise NotImplementedError

    def partitioning_keys(self) -> List[str]:
        return []

    def can_absorb_filter(self) -> bool:
        return False

    def can_absorb_limit(self) -> bool:
        return True

    def can_absorb_select(self) -> bool:
        return True

    def multiline_display(self) -> List[str]:
        return [type(self).__name__]

    def to_scan_tasks(self, pushdowns: Pushdowns) -> List[ScanTask]:
        raise NotImplementedError


class GeneratorScanOperator(ScanOperator):
    """Scan over pre-resolved entries, each loaded by a callback — the
    shared shape of the lake-format readers (Iceberg-with-deletes, Hudi
    MoR slices, Lance fragments), which resolve their file lists at plan
    time and materialize per entry at execution.

    ``entries``: list of (paths, load_fn) where ``load_fn(pushdowns)``
    yields RecordBatches. ``prune_fn(entry_index, pushdowns)`` → False
    drops an entry at planning (stats pruning)."""

    def __init__(self, schema: Schema, entries, label: str,
                 io_config=None, prune_fn=None,
                 entry_hints=None):
        self._schema = schema
        self._entries = entries
        self._label = label
        self._io_config = io_config
        self._prune_fn = prune_fn
        self._hints = entry_hints or [{} for _ in entries]

    def display(self) -> List[str]:
        return [self._label]

    def multiline_display(self) -> List[str]:
        return [self._label]

    def schema(self) -> Schema:
        return self._schema

    def to_scan_tasks(self, pushdowns: Pushdowns) -> List[ScanTask]:
        tasks = []
        for i, (paths, load_fn) in enumerate(self._entries):
            if self._prune_fn is not None \
                    and not self._prune_fn(i, pushdowns):
                continue
            def gen(load_fn=load_fn):
                yield from load_fn(pushdowns)
            hint = self._hints[i]
            tasks.append(ScanTask(
                list(paths), hint.get("format", "parquet"), self._schema,
                pushdowns, num_rows_hint=hint.get("rows"),
                size_bytes_hint=hint.get("size"), generator=gen,
                io_config=self._io_config))
        if not tasks:
            schema = self._schema
            tasks.append(ScanTask(
                [], "parquet", schema, pushdowns, num_rows_hint=0,
                generator=lambda: iter([_empty_batch(schema, pushdowns)])))
        return tasks


def _empty_batch(schema: Schema, pushdowns: Pushdowns):
    from ..recordbatch import RecordBatch
    if pushdowns.columns is not None:
        keep = [n for n in pushdowns.columns if n in schema]
        return RecordBatch.empty(schema.project(keep))
    return RecordBatch.empty(schema)


def _glob_local_files(pattern: str) -> List[str]:
    """The files ``pattern`` matches, sorted: what ``sorted(m for m in
    glob.glob(pattern, recursive=True) if os.path.isfile(m))`` gives,
    without a ``stat`` a match. Where the last part holds the magic
    (``<dir>/*.parquet``) its directories are listed with ``os.scandir``
    and a match's kind comes with its directory entry (``DirEntry`` stats
    by itself for a symlink, or where the file system gives no kind);
    a pattern whose last part is plain, or that holds a ``**`` (``glob``
    treats a leading one apart), is left to ``glob``."""
    head, tail = os.path.split(pattern)
    if not _glob.has_magic(tail) or "**" in pattern:
        return sorted(m for m in _glob.glob(pattern, recursive=True)
                      if os.path.isfile(m))
    dirs = _glob.glob(head) if _glob.has_magic(head) else [head]
    out: List[str] = []
    for d in dirs:
        try:
            with os.scandir(d or os.curdir) as it:
                entries = {e.name: e for e in it}
        except OSError:  # not a directory, or gone: matches nothing
            continue
        names = entries if tail.startswith(".") \
            else [n for n in entries if not n.startswith(".")]  # as glob
        for n in fnmatch.filter(names, tail):
            try:
                if entries[n].is_file():
                    out.append(os.path.join(d, n))
            except OSError:  # as os.path.isfile: not a file
                pass
    return sorted(out)


def glob_paths(path_or_paths, io_config=None) -> List[str]:
    """Local / file:// / remote (s3://) glob expansion (fanout-style,
    reference ``object_store_glob.rs``). Directories expand to their
    files. The listing stats no file (:func:`_glob_local_files`) and
    takes no identity: a ``DataFrame`` may be built long before it is
    collected."""
    paths = [path_or_paths] if isinstance(path_or_paths, str) else list(path_or_paths)
    out: List[str] = []
    for p in paths:
        if p.startswith("file://"):
            p = p[7:]
        if "://" in p and not p.startswith("file://"):
            from .object_io import get_io_client
            client = get_io_client(io_config)
            if any(ch in p for ch in "*?[]"):
                out.extend(client.glob(p))
            else:
                out.append(p)
            continue
        if any(ch in p for ch in "*?[]"):
            out.extend(_glob_local_files(p))
        elif os.path.isdir(p):
            for root, _, files in sorted(os.walk(p)):
                for f in sorted(files):
                    if not f.startswith((".", "_")):
                        out.append(os.path.join(root, f))
        else:
            out.append(p)
    if not out:
        raise FileNotFoundError(f"no files found for {path_or_paths!r}")
    return out


class GlobScanOperator(ScanOperator):
    """Scan over globbed files with schema inference from the first file
    (reference: ``glob.rs:28``) plus hive partition-value inference
    (``hive.rs``). A local file is ``stat``-ed once a query: when the
    scan's tasks are made (``to_scan_tasks``, on every ``collect()``), with
    all the scan's files in one batch (``footers.identities``). That
    ``(st_size, st_mtime_ns)`` finds a Parquet file's footer in
    ``footers.FooterStore`` and travels on the ``ScanTask``, where
    ``device/cache.task_fingerprint`` reads it: the footer a task was
    planned from and the columns the HBM cache answers with hang on the
    same ``stat``. Building the operator (the listing) stats no file."""

    def __init__(self, paths, file_format: str,
                 schema: Optional[Schema] = None,
                 format_options: Optional[Dict[str, Any]] = None,
                 hive_partitioning: bool = False,
                 io_config: Any = None):
        from . import readers
        self._io_config = io_config
        self._paths = glob_paths(paths, io_config)
        self._format = file_format
        self._options = format_options or {}
        self._hive = hive_partitioning
        self._hive_fields: Dict[str, DataType] = {}
        if schema is None:
            schema = readers.infer_schema(self._paths[0], file_format,
                                          self._options, io_config)
        if hive_partitioning:
            # union keys/types across ALL globbed paths — inferring from
            # the first path alone silently drops the partition columns of
            # mixed-key layouts (and types from a single value misjudge
            # e.g. a first partition that happens to look numeric)
            values: Dict[str, List[Any]] = {}
            for p in self._paths:
                for k, v in _hive_values(p).items():
                    values.setdefault(k, []).append(v)
            for k, vs in values.items():
                self._hive_fields[k] = DataType.infer_from_pylist(vs)
            schema = schema.non_distinct_union(
                Schema([Field(k, t) for k, t in self._hive_fields.items()]))
        self._schema = schema

    def schema(self) -> Schema:
        return self._schema

    def partitioning_keys(self) -> List[str]:
        return list(self._hive_fields)

    def multiline_display(self) -> List[str]:
        return [f"GlobScanOperator({self._format})",
                f"paths = {self._paths[:3]}{'…' if len(self._paths) > 3 else ''}"]

    def to_scan_tasks(self, pushdowns: Pushdowns) -> List[ScanTask]:
        from . import read_planner as rp, readers
        from .. import tracing
        from ..context import get_context
        cfg = get_context().execution_config
        idents = dict(zip(self._paths, footers.identities(self._paths)))
        tracing.tally("files_planned", len(self._paths))

        def plan_one(p: str) -> List[ScanTask]:
            pv = {}
            if self._hive:
                # missing-key → null fill: every task carries the UNION's
                # keys so a path lacking one still materializes the column
                vals = _hive_values(p)
                pv = {k: vals.get(k) for k in self._hive_fields}
            return readers.make_scan_tasks(
                p, self._format, self._schema, pushdowns, self._options, pv,
                self._io_config, idents[p])

        remote = [p for p in self._paths if "://" in p
                  and not p.startswith("file://")]
        if len(remote) > 1 and not rp.scan_sequential_fallback():
            # footer fetches dominate multi-file remote planning (one RTT
            # chain per file) — fan them over the IO pool, order preserved
            from .object_io import io_pool
            futs = [io_pool().submit(plan_one, p) for p in self._paths]
            groups = [f.result() for f in futs]
        else:
            groups = [plan_one(p) for p in self._paths]
        tasks: List[ScanTask] = [t for g in groups for t in g]
        tasks = split_scan_tasks(tasks, cfg.scan_tasks_max_size_bytes,
                                 cfg.parquet_split_row_groups_max_files)
        return merge_scan_tasks(tasks, cfg.scan_tasks_min_size_bytes,
                                cfg.scan_tasks_max_size_bytes,
                                cfg.max_sources_per_scan_task)


def _hive_values(path: str) -> Dict[str, Any]:
    out = {}
    for part in path.split(os.sep):
        if "=" in part and not part.startswith("."):
            k, _, v = part.partition("=")
            if k and v and "." not in v:
                out[k] = v
    return out


def split_scan_tasks(tasks: List[ScanTask], max_size: int,
                     max_files: int) -> List[ScanTask]:
    """Split oversized single-file parquet tasks into per-row-group-range
    tasks (reference: ``scan_task_iters/split_parquet``). Only the first
    ``max_files`` oversized files pay the metadata fetch; a limit pushdown
    disables splitting (the limit is served from the file head)."""
    out: List[ScanTask] = []
    split_budget = max_files
    for t in tasks:
        sz = t.size_bytes()
        if (t.file_format != "parquet" or len(t.paths) != 1
                or t.pushdowns.limit is not None or t.row_groups is not None
                or sz is None or sz <= max_size or split_budget <= 0):
            out.append(t)
            continue
        split_budget -= 1
        md = getattr(t, "pq_metadata", None)
        if md is None:
            try:
                md = footers.footer(t.paths[0], t.io_config,
                                    t.identity(0)).metadata
            except Exception:
                out.append(t)
                continue
        if md.num_row_groups <= 1:
            out.append(t)
            continue
        group: List[int] = []
        gsize = grows = 0
        for g in range(md.num_row_groups):
            rg = md.row_group(g)
            if group and gsize + rg.total_byte_size > max_size:
                out.append(ScanTask(t.paths, "parquet", t.schema, t.pushdowns,
                                    grows, gsize, [group], t.format_options,
                                    t.partition_values,
                                    identities=t.identities))
                group, gsize, grows = [], 0, 0
            group.append(g)
            gsize += rg.total_byte_size
            grows += rg.num_rows
        if group:
            out.append(ScanTask(t.paths, "parquet", t.schema, t.pushdowns,
                                grows, gsize, [group], t.format_options,
                                t.partition_values,
                                identities=t.identities))
    return out


def merge_scan_tasks(tasks: List[ScanTask], min_size: int, max_size: int,
                     max_sources: int) -> List[ScanTask]:
    """Merge small adjacent tasks into 96–384MB targets
    (reference: ``scan_task_iters``' merge-by-size)."""
    out: List[ScanTask] = []
    acc: Optional[ScanTask] = None
    acc_size = 0
    for t in tasks:
        sz = t.size_bytes() or max_size  # unknown size → don't merge
        limited = t.pushdowns.limit is not None
        if (acc is not None and not limited
                and acc_size + sz <= max_size
                and len(acc.paths) + len(t.paths) <= max_sources
                and acc.file_format == t.file_format
                and acc.row_groups is None and t.row_groups is None
                and acc.partition_values == t.partition_values):
            acc = ScanTask(acc.paths + t.paths, acc.file_format, acc.schema,
                           acc.pushdowns,
                           None if (acc._num_rows is None or t._num_rows is None)
                           else acc._num_rows + t._num_rows,
                           acc_size + sz, None, acc.format_options,
                           acc.partition_values,
                           identities=None if (acc.identities is None
                                               or t.identities is None)
                           else acc.identities + t.identities)
            acc_size += sz
            if acc_size >= min_size:
                out.append(acc)
                acc, acc_size = None, 0
            continue
        if acc is not None:
            out.append(acc)
            acc, acc_size = None, 0
        if sz >= min_size or limited:
            out.append(t)
        else:
            acc, acc_size = t, sz
    if acc is not None:
        out.append(acc)
    return out


class InMemoryScanOperator(ScanOperator):
    """Scan over already-materialized partitions (cache entries)."""

    def __init__(self, schema: Schema, partitions):
        self._schema = schema
        self._parts = partitions

    def schema(self) -> Schema:
        return self._schema

    def to_scan_tasks(self, pushdowns: Pushdowns) -> List[ScanTask]:
        tasks = []
        for p in self._parts:
            def gen(p=p):
                return iter(p.batches())
            tasks.append(ScanTask([], "memory", self._schema, pushdowns,
                                  p.metadata_num_rows(), None, generator=gen))
        return tasks
