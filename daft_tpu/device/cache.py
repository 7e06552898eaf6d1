"""HBM-resident device column cache.

The TPU sits behind a transfer link that is orders of magnitude slower than
host RAM (not yet measured on the attached chip — ``chip_smoke.py``
prints the profile ``costmodel.link_profile`` measures), so the device
tier can only win when hot columns *stay resident in HBM across queries* —
the TPU-native analogue of the reference's ``PartitionSetCache``
(``daft/runners/runner.py:22-35``) one level down: instead of caching result
partitions host-side, we cache *encoded scan columns* device-side, keyed by
scan-task fingerprint.

Granularity is (task, column): different queries touching different column
subsets of the same file share entries. Entries are LRU-evicted to a byte
budget (``DAFT_TPU_HBM_CACHE_BYTES``, default 8 GiB — leaves headroom on a
16 GiB v5e chip for kernel workspace).

Invalidation: the fingerprint covers file paths, sizes, mtimes, row-group
selection and row-affecting pushdowns, so a changed file re-encodes.
In-memory / generator-backed tasks have no stable identity and bypass the
cache.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import column as dcol


def _budget() -> int:
    # 8 GiB of a 16 GiB v5e: encoded columns are compact (f64 rides f32,
    # strings ride i32 codes), and the grouped-agg workspace peaks well
    # under the remaining half. 4 GiB (r4) turned away SF10's ~3.4 GiB
    # hot-column set that residency would have repaid.
    from ..analysis import knobs
    return knobs.env_bytes("DAFT_TPU_HBM_CACHE_BYTES")


def task_fingerprint(task) -> Optional[Tuple]:
    """Stable identity of a scan task's *loaded rows*, or None if the task
    has no cacheable identity (generator source, unstat-able paths)."""
    if getattr(task, "generator", None) is not None:
        return None
    try:
        stats = []
        for p in task.paths:
            if not os.path.exists(p):
                return None  # remote path: no cheap invalidation signal
            st = os.stat(p)
            stats.append((p, st.st_size, st.st_mtime_ns))
    except OSError:
        return None
    pd = task.pushdowns
    filt = pd.filters._key() if getattr(pd, "filters", None) is not None \
        else None
    rg = tuple(tuple(r) if r is not None else None
               for r in task.row_groups) if task.row_groups else None
    return (tuple(stats), task.file_format, rg, filt, pd.limit)


class _Entry:
    __slots__ = ("col", "nbytes")

    def __init__(self, col: dcol.DeviceColumn, nbytes: int):
        self.col = col
        self.nbytes = nbytes


class DeviceColumnCache:
    def __init__(self):
        self._lock = threading.Lock()
        self._cols: "OrderedDict[Tuple, _Entry]" = OrderedDict()
        self._masks: "OrderedDict[Tuple, Tuple]" = OrderedDict()  # fp -> (mask, rows, cap)
        self._bytes = 0
        # since the process started (``clear()`` empties the cache, not
        # these): whole-table lookups served / not, bytes put, bytes
        # pushed out by the budget
        self._hits = self._misses = 0
        self._put_bytes = self._evicted_bytes = 0

    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"entries": len(self._cols), "bytes": self._bytes,
                    "hits": self._hits, "misses": self._misses,
                    "put_bytes": self._put_bytes,
                    "evicted_bytes": self._evicted_bytes}

    def clear(self) -> None:
        with self._lock:
            self._cols.clear()
            self._masks.clear()
            self._bytes = 0

    # ------------------------------------------------------------------
    def get_table(self, fp: Tuple, cols: List[str]
                  ) -> Optional[dcol.DeviceTable]:
        """All requested columns cached → assembled DeviceTable, else None."""
        with self._lock:
            mask = self._masks.get(fp)
            entries = [self._cols.get((fp, c)) for c in cols] \
                if mask is not None else [None]
            if any(e is None for e in entries):
                self._misses += 1
                return None
            self._hits += 1
            for c in cols:
                self._cols.move_to_end((fp, c))
            self._masks.move_to_end(fp)
            row_mask, rows, cap = mask
            return dcol.DeviceTable(
                {c: e.col for c, e in zip(cols, entries)}, row_mask, rows,
                cap, resident=True)

    def put_table(self, fp: Tuple, dt: dcol.DeviceTable) -> None:
        from .. import tracing
        add = 0
        sized = []
        for name, col in dt.columns.items():
            nbytes = int(col.data.nbytes) + int(col.validity.nbytes)
            sized.append((name, col, nbytes))
            add += nbytes
        if add > _budget():
            return
        # bookkeeping only: the planes were put by ``encode_batch``,
        # whose ``device:put`` spans carry their bytes
        with tracing.span("device:put", lane="device",
                          attrs={"cached": 1, "cached_bytes": add}):
            # the caller's table now SHARES buffers with the cache — it
            # must never be donated to a fused program from here on
            dt.resident = True
            with self._lock:
                self._masks[fp] = (dt.row_mask, dt.row_count, dt.capacity)
                for name, col, nbytes in sized:
                    key = (fp, name)
                    old = self._cols.pop(key, None)
                    if old is not None:
                        self._bytes -= old.nbytes
                    self._cols[key] = _Entry(col, nbytes)
                    self._bytes += nbytes
                self._put_bytes += add
                self._evict_locked()

    def _evict_locked(self) -> None:
        budget = _budget()
        while self._bytes > budget and self._cols:
            _, e = self._cols.popitem(last=False)
            self._bytes -= e.nbytes
            self._evicted_bytes += e.nbytes
        live_fps = {k[0] for k in self._cols}
        for fp in [f for f in self._masks if f not in live_fps]:
            del self._masks[fp]


_cache: Optional[DeviceColumnCache] = None
_cache_lock = threading.Lock()


def get_cache() -> DeviceColumnCache:
    global _cache
    with _cache_lock:
        if _cache is None:
            _cache = DeviceColumnCache()
        return _cache
