"""Parquet footers the process already holds.

Planning a scan needs each file's footer: its schema, the rows and bytes of
its row groups, and the min / max / null count of every column chunk, which
``readers.make_scan_tasks`` prunes row groups by. Reading one costs an
open, two reads, a thrift parse and a pyarrow object per row group and
column asked; a query over 160 files paid that 160 times, and the next
query paid it again for the same unchanged files.

:class:`FooterStore` keeps, per local file, the pyarrow ``FileMetaData``
and a :class:`Footer` digest of it in plain Python values, under the
identity the HBM column cache (``device/cache.task_fingerprint``) already
trusts a file's *data* by: ``(path, st_size, st_mtime_ns)``. A hit costs
a dictionary lookup; a file rewritten, replaced or touched misses and is
read anew. Only facts about a *file* are kept: every query still builds
its own scan tasks and prunes row groups by its own filter (no plan, task
list or identity is cached). A remote path, or one that cannot be
``stat``-ed, is read every time, as before.

Where a file is ``stat``-ed: once a query, in :func:`identities`, which
``GlobScanOperator.to_scan_tasks`` calls with all the scan's paths when it
makes the scan's tasks (the ``stat``s go out together on the IO pool).
The identity is handed to :meth:`FooterStore.get` and travels on the
``ScanTask`` to ``device/cache.task_fingerprint``. ``get`` stats for
itself only when given none (schema inference at ``read_parquet``, the
catalog readers). Every such call is tallied on the query's trace as
``file_stats``, beside ``files_planned`` (``summary()["files"]``).
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import pyarrow.parquet as pq

from .. import tracing

#: entries the store keeps (least recently used out first); a footer of a
#: 16-column, 4-row-group file holds ~10 KB serialized
MAX_ENTRIES = 4096


class Footer:
    """One file's footer: the ``FileMetaData`` (shared, immutable) and
    what planning reads of it, as plain values.

    ``columns[path_in_schema][g]`` is ``None`` where row group ``g`` has
    no statistics for the column, else ``(has_min_max, min, max,
    null_count)`` with ``null_count`` None where the footer has none."""

    __slots__ = ("metadata", "group_rows", "group_bytes", "num_rows",
                 "total_bytes", "columns")

    def __init__(self, metadata):
        self.metadata = metadata
        self.group_rows: List[int] = []
        self.group_bytes: List[int] = []
        self.columns: Dict[str, List[Optional[Tuple]]] = {}
        for g in range(metadata.num_row_groups):
            rg = metadata.row_group(g)
            self.group_rows.append(rg.num_rows)
            self.group_bytes.append(rg.total_byte_size)
            for i in range(rg.num_columns):
                chunk = rg.column(i)
                st = chunk.statistics
                if st is not None:
                    has = st.has_min_max
                    st = (has, st.min if has else None,
                          st.max if has else None,
                          st.null_count if st.has_null_count else None)
                self.columns.setdefault(chunk.path_in_schema, []).append(st)
        self.num_rows = metadata.num_rows
        self.total_bytes = sum(self.group_bytes)


class FooterStore:
    """Path -> ((size, mtime_ns), :class:`Footer`), LRU, one lock."""

    def __init__(self, max_entries: int = MAX_ENTRIES):
        self._max = max_entries
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, Tuple[Tuple[int, int], Footer]]" \
            = OrderedDict()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def get(self, path: str, io_config: Any = None,
            identity: Optional[Tuple[int, int]] = None) -> Footer:
        """The footer of the file ``path`` was when ``identity`` (its
        ``(st_size, st_mtime_ns)``; read here when None) was taken: from
        the store when that is the stored one, else read (and, for a
        local file, stored and tallied on the current trace). Raises what
        the read raises."""
        ident = identity
        if ident is None and "://" not in path:  # handed none: stat here
            tracing.tally("file_stats")
            ident, = _stat_each([path])
        if ident is None:  # remote, or gone: no identity to keep it under
            from .readers import _open_ranged
            return Footer(pq.ParquetFile(
                _open_ranged(path, io_config)).metadata)
        with self._lock:
            held = self._entries.get(path)
            if held is not None and held[0] == ident:
                self._entries.move_to_end(path)
            else:
                held = None
        if held is not None:
            tracing.tally("footers_from_store")
            return held[1]
        # outside the lock: two misses on one file may both read it, and
        # either result is kept
        footer = Footer(pq.ParquetFile(path).metadata)
        tracing.tally("footers_read")
        with self._lock:
            self._entries[path] = (ident, footer)
            self._entries.move_to_end(path)  # a replaced entry keeps its place
            while len(self._entries) > self._max:
                self._entries.popitem(last=False)
        return footer


_STORE = FooterStore()


def get_store() -> FooterStore:
    return _STORE


def footer(path: str, io_config: Any = None,
           identity: Optional[Tuple[int, int]] = None) -> Footer:
    """:meth:`FooterStore.get` on the process's store."""
    return _STORE.get(path, io_config, identity)


#: the batch of one scan's ``stat``s goes out in at most this many chunks
_STAT_CHUNKS = 8


def _stat_each(paths: List[str]) -> List[Optional[Tuple[int, int]]]:
    out: List[Optional[Tuple[int, int]]] = []
    for p in paths:
        try:
            st = os.stat(p)
            out.append((st.st_size, st.st_mtime_ns))
        except OSError:
            out.append(None)
    return out


def identities(paths: List[str]) -> List[Optional[Tuple[int, int]]]:
    """``(st_size, st_mtime_ns)`` of each path as it is now, in order;
    None for a path that cannot be ``stat``-ed (remote, gone). The local
    paths are stat-ed together: in a few chunks on the IO pool
    (``os.stat`` drops the GIL), the last one on the calling thread.
    Tallied as ``file_stats`` on the current trace."""
    todo = [p for p in paths if "://" not in p]
    if not todo:
        return [None] * len(paths)
    tracing.tally("file_stats", len(todo))
    step = -(-len(todo) // _STAT_CHUNKS)
    chunks = [todo[k:k + step] for k in range(0, len(todo), step)]
    from .object_io import io_pool
    futs = [io_pool().submit(_stat_each, c) for c in chunks[:-1]]
    last = _stat_each(chunks[-1])
    got = iter([ident for f in futs for ident in f.result()] + last)
    return [None if "://" in p else next(got) for p in paths]
