"""What the plain references share: reading the Parquet files with pyarrow
and the rounding hook of the lower-precision control.

A reference computes a query's answer from the same files in float64 with
pyarrow, pandas and numpy. It imports nothing of ``daft_tpu`` (not the
engine's host tier either) and takes nothing the engine made. Every
arithmetic result passes through ``rnd``: the identity for the reference,
:func:`bf16` for the control (values as bfloat16 would hold them, summed
exactly, which is the gentlest way to compute below float32)."""

from __future__ import annotations

import glob
import os
from typing import Callable, Iterator, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

Rnd = Callable[[np.ndarray], np.ndarray]


def exact(x):
    return x


def bf16(x):
    import ml_dtypes
    return (np.asarray(x, dtype=np.float32).astype(ml_dtypes.bfloat16)
            .astype(np.float64))


def files(root: str, table: str) -> list:
    found = sorted(glob.glob(os.path.join(root, table, "*.parquet")))
    if not found:
        raise FileNotFoundError(f"no parquet files of {table} under {root}")
    return found


def tables(root: str, table: str, columns: Sequence[str],
           filters=None) -> Iterator[pa.Table]:
    """One arrow table per file, so that a reference over SF10's
    ``lineitem`` never holds more than one part."""
    for path in files(root, table):
        yield pq.read_table(path, columns=list(columns), filters=filters)


def frame(root: str, table: str, columns: Sequence[str], filters=None):
    import pandas as pd
    return pd.concat(
        [t.to_pandas(date_as_object=False)
         for t in tables(root, table, columns, filters)],
        ignore_index=True)


def f64(table: pa.Table, name: str) -> np.ndarray:
    return table.column(name).to_numpy().astype(np.float64, copy=False)
