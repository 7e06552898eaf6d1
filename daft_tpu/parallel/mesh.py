"""Process-wide device mesh singleton.

The engine's collective exchanges run over one 1-D ``data`` mesh spanning
every visible device (virtual CPU devices under
``xla_force_host_platform_device_count`` in tests, real chips on a pod).
``DAFT_TPU_MESH_DEVICES`` caps the axis length; mesh construction is guarded
behind the watchdog-probed backend (device/backend.py) so a wedged plugin
can't hang planning.
"""

from __future__ import annotations

import threading
from typing import Optional

_lock = threading.RLock()   # re-entrant: get_mesh holds it across mesh_size
_mesh = None
_size: Optional[int] = None
_scan_devices: Optional[list] = None


def mesh_size() -> int:
    """Number of devices the exchange mesh would span (0 = no device)."""
    global _size
    if _size is not None:
        return _size
    with _lock:
        if _size is not None:
            return _size
        from ..device import backend
        if backend.backend_name() is None:
            _size = 0
            return 0
        import jax

        n = len(jax.devices())
        from ..analysis import knobs
        cap = knobs.env_int("DAFT_TPU_MESH_DEVICES")
        if cap is not None:
            n = min(n, cap)
        _size = n
        return n


def scan_devices() -> list:
    """The chips the scan path spreads its tables over: the first
    :func:`mesh_size` of ``jax.devices()`` (so ``DAFT_TPU_MESH_DEVICES``
    caps both). A scan task's table lives whole on one of them
    (``DeviceTable.chip`` indexes this list) and the HBM column cache
    keeps one budget per entry. One entry or none: nothing is placed,
    every plane goes to the default device as before."""
    global _scan_devices
    if _scan_devices is None:
        n = mesh_size()
        with _lock:
            if _scan_devices is None:
                import jax
                _scan_devices = list(jax.devices()[:n]) if n else []
    return _scan_devices


#: legacy static admission floor, now only the FALLBACK when the cost
#: model cannot price a collective (no calibrated rates at all);
#: ``DAFT_TPU_MESH_MIN_ROWS`` (when set) force-overrides the cost model
#: entirely — ``0`` forces the mesh (the knob the mesh-correctness tests
#: and the multichip dryrun set), ``N`` requires at least N rows
_MESH_MIN_ROWS = 65536


def mesh_min_rows() -> int:
    from ..analysis import knobs
    v = knobs.env_int("DAFT_TPU_MESH_MIN_ROWS", default=None)
    return v if v is not None else _MESH_MIN_ROWS


def mesh_admits(rows: Optional[int], row_bytes: float = 32.0) -> bool:
    """Admission for a mesh collective (exchange agg, hash repartition).

    ``DAFT_TPU_MESH_MIN_ROWS`` set → force-override: the static row floor
    decides exactly as before (``0`` forces the mesh). Unset → the cost
    model prices the collective (dispatch + amortized compile + bytes
    over the calibrated ICI rate, ``costmodel.ici_bps``) against one
    host hash-partition pass — so tiny aggs stop paying collective
    compile+dispatch while medium, wide-row ones stop being wrongly
    declined by a width-blind row count."""
    from ..analysis import knobs
    v = knobs.env_int("DAFT_TPU_MESH_MIN_ROWS", default=None)
    if v is not None:
        return rows is None or rows >= v
    try:
        from ..device import costmodel
        return costmodel.mesh_exchange_wins(rows, row_bytes, mesh_size())
    except Exception:
        return rows is None or rows >= _MESH_MIN_ROWS


def get_mesh():
    global _mesh
    with _lock:
        if _mesh is None:
            from . import exchange
            n = mesh_size()
            if n < 1:
                return None
            _mesh = exchange.make_mesh(n)
        return _mesh


def reset_for_tests() -> None:
    global _mesh, _size, _scan_devices
    with _lock:
        _mesh = None
        _size = None
        _scan_devices = None
