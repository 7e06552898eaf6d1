"""The one place where the benchmark touches the program: the public query
path, and the counters it reads. Everything else under ``chipbench/`` is
the yardstick and imports nothing of ``daft_tpu``."""

from __future__ import annotations

import os
from typing import Dict


class Engine:
    """daft_tpu over the Parquet files under ``root``, in ``auto`` mode
    with no knob set (the host-tier passes of a traced run set
    ``DAFT_TPU_DEVICE=0`` and take it away again)."""

    def __init__(self, root: str):
        import daft_tpu
        from daft_tpu.device import backend, cache, costmodel, runtime
        self._read_parquet = daft_tpu.read_parquet
        self._backend = backend
        self._cache = cache
        self._costmodel = costmodel
        self._runtime = runtime
        self.root = root
        os.environ.pop("DAFT_TPU_DEVICE", None)
        os.environ.pop("DAFT_TPU_DEVICE_FORCE", None)

    def backend_name(self):
        name = self._backend.backend_name()
        err = self._backend.probe_error()
        if err is not None:
            raise RuntimeError(f"daft_tpu's backend probe failed: {err}")
        return name

    def get_df(self, table: str):
        return self._read_parquet(f"{self.root}/{table}/*.parquet")

    def host_tier(self, on: bool) -> None:
        if on:
            os.environ["DAFT_TPU_DEVICE"] = "0"
        else:
            os.environ.pop("DAFT_TPU_DEVICE", None)

    def clear_cache(self) -> None:
        self._cache.get_cache().clear()

    def cache_bytes(self) -> int:
        return int(self._cache.get_cache().stats()["bytes"])

    def failures(self) -> int:
        return sum(v["count"] for v in
                   self._runtime.device_failures().values())

    def first_failure(self) -> str:
        for site, v in self._runtime.device_failures().items():
            return f"{site}: {v.get('first_error')}"
        return ""

    def counters(self) -> Dict[str, Dict[str, float]]:
        """Decisions of the dispatch gate (device / host, all kinds summed)
        and dispatches per kernel family, since the process started."""
        dec = {"device": 0, "host": 0}
        for v in list(self._costmodel.decision_counts.values()):
            dec["device"] += v.get("device", 0)
            dec["host"] += v.get("host", 0)
        led = self._costmodel.ledger_snapshot(raw=True)
        return {"decisions": dec,
                "dispatches": {k: v.get("dispatches", 0)
                               for k, v in led.items()}}
