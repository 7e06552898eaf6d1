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
subsets of the same file share entries. The cache spans every chip the scan
path places tables on (``parallel.mesh.scan_devices()``), and everything it
counts is **per chip**: a task's columns, validity and row mask live whole
on one chip (a table is never split), each chip has its own LRU and its own
byte budget (``DAFT_TPU_HBM_CACHE_BYTES``, default 8 GiB of a v5e chip's
16 GiB — headroom for kernel workspace), and filling one chip evicts only
that chip's entries. A task's chip is remembered with its entries, so a
later scan of the same file finds it, and runs, where the first put it.

Invalidation: the fingerprint covers file paths, sizes, mtimes, row-group
selection and row-affecting pushdowns, so a changed file re-encodes. The
same ``(path, st_size, st_mtime_ns)`` identity is what ``io/footers.py``'s
store keeps a file's Parquet footer under, so a scan is planned from held
metadata exactly as long as its columns may be served from here.
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
    # one chip's budget, 8 GiB of a 16 GiB v5e: encoded columns are
    # compact (f64 rides f32, strings ride i32 codes), and the
    # grouped-agg workspace peaks well under the remaining half. 4 GiB
    # (r4) turned away SF10's ~3.4 GiB hot-column set that residency
    # would have repaid.
    from ..analysis import knobs
    return knobs.env_bytes("DAFT_TPU_HBM_CACHE_BYTES")


def task_fingerprint(task) -> Optional[Tuple]:
    """Stable identity of a scan task's *loaded rows*, or None if the task
    has no cacheable identity (generator source, unstat-able paths). Built
    from the ``(st_size, st_mtime_ns)`` the task carries for its paths;
    a task that carries none (catalog readers, hand-made tasks) is
    stat-ed here."""
    if getattr(task, "generator", None) is not None:
        return None
    carried = getattr(task, "identities", None)
    if carried is not None:
        # the identities the task was planned under (``io/footers.py``):
        # the same ``stat`` that chose its footer
        stats = [(p, *ident) for p, ident in zip(task.paths, carried)]
    else:
        from .. import tracing
        try:
            stats = []
            for p in task.paths:
                tracing.tally("file_stats")
                if not os.path.exists(p):
                    return None  # remote path: no cheap invalidation signal
                tracing.tally("file_stats")
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


class _Chip:
    """One chip's share of the cache: its own LRU order, bytes and
    evictions."""
    __slots__ = ("cols", "masks", "bytes", "evicted_bytes")

    def __init__(self):
        self.cols: "OrderedDict[Tuple, _Entry]" = OrderedDict()
        # fp -> (row mask, rows, capacity, DeviceTable.chip)
        self.masks: "OrderedDict[Tuple, Tuple]" = OrderedDict()
        self.bytes = 0
        self.evicted_bytes = 0

    def drop(self, fp: Tuple) -> None:
        self.masks.pop(fp, None)
        for key in [k for k in self.cols if k[0] == fp]:
            self.bytes -= self.cols.pop(key).nbytes


class DeviceColumnCache:
    def __init__(self):
        self._lock = threading.Lock()
        #: chip index -> its share; a table with ``chip`` None (one
        #: visible chip) is kept under 0
        self._chips: Dict[int, _Chip] = {}
        # since the process started (``clear()`` empties the cache, not
        # these): whole-table lookups served / not, bytes put
        self._hits = self._misses = 0
        self._put_bytes = 0
        #: the assembled inputs of the fused aggregate's round launches
        #: (``fragment._dispatch_round``), kept beside the tables they
        #: are made of: ids of the member planes -> (the planes, what
        #: was built over them). An entry holds its planes, so no id is
        #: reused while it lives; a global array holds its shards'
        #: buffers, so every entry goes when ANY plane leaves the cache
        #: (eviction, a table put again or on another chip, ``clear``):
        #: a round is cheap to assemble again, and HBM a dropped table
        #: held is not kept
        self._rounds: Dict[Tuple, Tuple] = {}

    # ------------------------------------------------------------------
    def stats(self) -> Dict:
        """``entries``, ``bytes`` and ``evicted_bytes`` summed over the
        chips, and per chip under ``chips`` (chip index -> the same three;
        only chips that ever held a table)."""
        with self._lock:
            chips = {k: {"entries": len(c.cols), "bytes": c.bytes,
                         "evicted_bytes": c.evicted_bytes}
                     for k, c in sorted(self._chips.items())}
            out = {key: sum(c[key] for c in chips.values())
                   for key in ("entries", "bytes", "evicted_bytes")}
            out.update(hits=self._hits, misses=self._misses,
                       put_bytes=self._put_bytes, chips=chips,
                       rounds=len(self._rounds))
            return out

    def clear(self) -> None:
        """Empty every chip."""
        with self._lock:
            for c in self._chips.values():
                c.cols.clear()
                c.masks.clear()
                c.bytes = 0
            self._rounds.clear()

    def _find_locked(self, fp: Tuple):
        """(the chip's share that holds this task, its mask entry), or
        (None, None)."""
        for c in self._chips.values():
            mask = c.masks.get(fp)
            if mask is not None:
                return c, mask
        return None, None

    def home(self, fp: Tuple) -> Optional[int]:
        """The chip that holds this task's planes (``DeviceTable.chip``
        as they were put: None too for a table put unplaced, with one
        chip visible), or None when no chip holds any: a query that
        needs more of the task's columns puts them beside the others."""
        with self._lock:
            mask = self._find_locked(fp)[1]
        return None if mask is None else mask[3]

    # ------------------------------------------------------------------
    def get_table(self, fp: Tuple, cols: List[str]
                  ) -> Optional[dcol.DeviceTable]:
        """All requested columns cached → assembled DeviceTable, else None."""
        with self._lock:
            c, mask = self._find_locked(fp)
            entries = [c.cols.get((fp, n)) for n in cols] \
                if mask is not None else [None]
            if any(e is None for e in entries):
                self._misses += 1
                return None
            self._hits += 1
            for n in cols:
                c.cols.move_to_end((fp, n))
            c.masks.move_to_end(fp)
            row_mask, rows, cap, chip = mask
            return dcol.DeviceTable(
                {n: e.col for n, e in zip(cols, entries)}, row_mask, rows,
                cap, resident=True, chip=chip)

    def round_inputs(self, planes: Tuple, build):
        """``build()`` over ``planes`` (the planes of a round's resident
        tables, each the cache's own, in a fixed order), assembled once
        and kept until a plane leaves the cache. Two threads that miss
        together both build; the later entry stands. A table evicted
        between its lookup and its launch leaves an entry behind that
        the next eviction drops."""
        key = tuple(map(id, planes))
        with self._lock:
            hit = self._rounds.get(key)
        if hit is not None:
            return hit[1], True
        built = build()
        with self._lock:
            self._rounds[key] = (planes, built)
        return built, False

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
        at = dt.chip or 0
        # bookkeeping only: the planes were put by ``encode_batch``,
        # whose ``device:put`` spans carry their bytes
        with tracing.span("device:put", lane="device",
                          attrs={"cached": 1, "cached_bytes": add,
                                 "chip": at}):
            # the caller's table now SHARES buffers with the cache — it
            # must never be donated to a fused program from here on
            dt.resident = True
            with self._lock:
                for k, other in self._chips.items():
                    if fp in other.masks:
                        # a table put again: its mask plane is replaced,
                        # its columns may be, wherever it lay
                        self._rounds.clear()
                    if k != at:     # one table, one chip
                        other.drop(fp)
                c = self._chips.setdefault(at, _Chip())
                c.masks[fp] = (dt.row_mask, dt.row_count, dt.capacity,
                               dt.chip)
                for name, col, nbytes in sized:
                    key = (fp, name)
                    old = c.cols.pop(key, None)
                    if old is not None:
                        c.bytes -= old.nbytes
                    c.cols[key] = _Entry(col, nbytes)
                    c.bytes += nbytes
                self._put_bytes += add
                self._evict_locked(c)

    def _evict_locked(self, c: _Chip) -> None:
        budget = _budget()
        while c.bytes > budget and c.cols:
            _, e = c.cols.popitem(last=False)
            c.bytes -= e.nbytes
            c.evicted_bytes += e.nbytes
            self._rounds.clear()
        live_fps = {k[0] for k in c.cols}
        for fp in [f for f in c.masks if f not in live_fps]:
            del c.masks[fp]


_cache: Optional[DeviceColumnCache] = None
_cache_lock = threading.Lock()


def get_cache() -> DeviceColumnCache:
    global _cache
    with _cache_lock:
        if _cache is None:
            _cache = DeviceColumnCache()
        return _cache
