"""Peaks of the chips the benchmark may run on, and the bytes a scan
aggregate has to read. Copied from ``costmodel.DEVICE_PEAKS`` (PR 23); a
device kind that is not here is an error, never a default."""

from __future__ import annotations

from typing import Dict, Sequence

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"peak_flops": 197e12, "hbm_bps": 819e9},
}

#: the fewest bytes a value of a column can take on the device. The chip
#: has no f64 (the engine rides it as f32), a date is a day count in i32,
#: and a key of a few distinct values needs one byte; integers keep 8.
MIN_BYTES = {"float": 4, "date": 4, "code": 1, "int": 8}


def scan_agg_bytes(rows: int, column_kinds: Sequence[str]) -> int:
    """The least HBM traffic of one fused scan aggregate over ``rows``
    rows: each column it reads, once, at :data:`MIN_BYTES`. A lower bound
    (validity planes, padding to the bucket and the output are left out),
    so a share of the roofline worked out from it is understated, never
    over 100% for a program that reads what it must."""
    return rows * sum(MIN_BYTES[k] for k in column_kinds)
