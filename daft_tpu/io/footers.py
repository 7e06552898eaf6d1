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
one ``os.stat`` and a dictionary lookup; a file rewritten, replaced or
touched misses and is read anew. Only facts about a *file* are kept:
every query still builds its own scan tasks and prunes row groups by its
own filter (no plan or task list is cached). A remote path, or one that
cannot be ``stat``-ed, is read every time, as before.
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

    def get(self, path: str, io_config: Any = None) -> Footer:
        """The footer of ``path`` as the file is now: from the store when
        its size and mtime are the stored ones, else read (and, for a
        local file, stored and tallied on the current trace). Raises what
        the read raises."""
        try:
            st = os.stat(path)
        except OSError:  # remote, or gone: no identity to keep it under
            from .readers import _open_ranged
            return Footer(pq.ParquetFile(
                _open_ranged(path, io_config)).metadata)
        ident = (st.st_size, st.st_mtime_ns)
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


def footer(path: str, io_config: Any = None) -> Footer:
    """:meth:`FooterStore.get` on the process's store."""
    return _STORE.get(path, io_config)
