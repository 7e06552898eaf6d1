"""Calibrated cost-model profile: measured history beats footer estimates.

The engine records everything — per-dispatch achieved rates in the MFU
ledger, shuffle wire rates at every fetch, per-query stat blocks in the
flight recorder — and until round 20 used none of it for the next query:
every ``costmodel.*_wins`` decision was priced from hard-coded dev-box
constants (``DEV_*_BPS``). This module closes loop (a) of the self-tuning
plan (ROADMAP item 4): a per-backend profile of OBSERVED constants,
learned with an EWMA update rule and persisted across processes
(``DAFT_TPU_CALIBRATION_DIR``), that overrides the hard-coded defaults
once a sample-count floor is met.

Calibrated names (one entry each, same units as the costmodel constant):

- ``DEV_VECTOR_BPS`` / ``DEV_AGG_BPS`` — achieved device bytes/s per
  kernel family, observed at every real dispatch through
  ``costmodel.ledger_record``;
- ``DEV_SORT_ROWS_PER_S`` / ``DEV_JOIN_ROWS_PER_S`` — achieved rows/s,
  same chokepoint;
- ``SHUFFLE_WIRE_BPS`` — achieved shuffle-fetch bytes/s, observed at
  ``shuffle_service.fetch_partition`` (sizable fetches only: tiny
  partitions measure RTT, not bandwidth);
- ``ICI_BPS`` — the marginal collective-exchange rate, observed whenever
  ``costmodel._measure_ici`` runs;
- ``NDV_FOOTER_RATIO`` — observed actual-groups / footer-NDV ratio
  (parquet min/max range NDV systematically OVER-predicts: a sparse key
  set reads as near-unique). ``shuffle_combine_wins`` damps footer NDV
  evidence by this ratio.

Contract with the chaos-determinism rules (r10/r14): under
``DAFT_TPU_CHAOS_SERIALIZE=1`` or an active fault plan the profile is
FROZEN — ``const()`` returns the hard-coded default and ``observe()``
drops the sample — so a chaos replay prices every decision exactly like
the recorded run, bit-identically.

Everything is gated on ``DAFT_TPU_CALIBRATION`` (default off; the
``ExecutionConfig.tpu_calibration`` mirror is the per-query spelling):
with the knob off this module is a handful of dict lookups returning
defaults, and the observation chokepoints are no-ops.
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
from typing import Dict, Optional

#: observations between opportunistic persists (plus a time throttle) —
#: a hot query must not fsync the profile per dispatch
_PERSIST_EVERY = 32
_PERSIST_MIN_INTERVAL_S = 5.0

#: calibrated-NDV damping is clamped: a ratio below this would let one
#: freak observation erase footer evidence entirely, above it would
#: inflate footer NDV past the row count the caller already clamps to
_NDV_RATIO_MIN = 1.0 / 64.0
_NDV_RATIO_MAX = 4.0

_lock = threading.Lock()
_profile: Optional[Dict[str, Dict[str, float]]] = None  # name → entry
_obs_since_persist = 0
_last_persist = 0.0
_history_ingested = False
_atexit_registered = False


# ------------------------------------------------------------------ knobs

def _cfg(field: str, default):
    try:
        from ..context import get_context
        return getattr(get_context().execution_config, field)
    except Exception:
        return default


def enabled() -> bool:
    """Master gate: env ``DAFT_TPU_CALIBRATION`` overrides the per-query
    ``ExecutionConfig.tpu_calibration`` mirror; default off."""
    from ..analysis import knobs
    raw = knobs.env_raw("DAFT_TPU_CALIBRATION")
    if raw is not None:
        return bool(knobs.env_bool("DAFT_TPU_CALIBRATION"))
    return bool(_cfg("tpu_calibration", False))


def frozen() -> bool:
    """Feedback state is frozen (reads return defaults, observations are
    dropped) whenever the chaos-determinism contract is active: replay
    must price every decision exactly like the recorded run."""
    from ..analysis import knobs
    if knobs.env_bool("DAFT_TPU_CHAOS_SERIALIZE"):
        return True
    try:
        from ..distributed.resilience import active_fault_plan
        return active_fault_plan() is not None
    except Exception:
        return False


def alpha() -> float:
    from ..analysis import knobs
    a = knobs.env_float("DAFT_TPU_CALIBRATION_ALPHA", default=None)
    if a is None:
        a = _cfg("tpu_calibration_alpha", 0.2)
    return min(max(float(a), 1e-3), 1.0)


def min_samples() -> int:
    from ..analysis import knobs
    n = knobs.env_int("DAFT_TPU_CALIBRATION_MIN_SAMPLES", default=None)
    if n is None:
        n = _cfg("tpu_calibration_min_samples", 8)
    return max(int(n), 1)


def profile_dir() -> Optional[str]:
    from ..analysis import knobs
    d = knobs.env_str("DAFT_TPU_CALIBRATION_DIR")
    if not d:
        d = _cfg("tpu_calibration_dir", "") or None
    return d or None


def _backend_name() -> str:
    try:
        from . import backend
        return backend.backend_name() or "cpu"
    except Exception:
        return "cpu"


def _path() -> Optional[str]:
    d = profile_dir()
    if not d:
        return None
    return os.path.join(d, f"calibration_{_backend_name()}.json")


# ------------------------------------------------------------- load/store

def _read_profile_file() -> Dict[str, Dict[str, float]]:
    """Parse the persisted profile (no locks held — pure file read)."""
    out: Dict[str, Dict[str, float]] = {}
    path = _path()
    if path:
        try:
            with open(path) as f:
                d = json.load(f)
            for name, e in (d.get("entries") or {}).items():
                v, n = float(e["value"]), float(e["samples"])
                if math.isfinite(v) and v > 0 and n > 0:
                    out[name] = {"value": v, "samples": n}
        except (OSError, ValueError, KeyError, TypeError):
            pass
    return out


def _ensure_loaded() -> None:
    """Lazy one-time profile load. The file read happens OUTSIDE the
    lock (a duplicate read in a race is harmless; first install wins).
    After the install, flight-recorder history seeds the profile once —
    the 'fresh processes start calibrated' channel (the nested
    ``observe``/``const`` calls the ingest makes re-enter here and
    return immediately on the installed profile)."""
    global _profile
    if _profile is None:
        loaded = _read_profile_file()
        with _lock:
            if _profile is None:
                _profile = loaded
    # not tied to the install above: a load that happened while
    # calibration was disabled must not skip the ingest forever (the
    # latch is set inside ingest_flight_history, before it observes,
    # so the nested re-entry from its own observe() calls is a no-op)
    if not _history_ingested and enabled() and not frozen():
        ingest_flight_history()


def _load_locked() -> Dict[str, Dict[str, float]]:
    """The live profile dict; callers hold ``_lock`` and have called
    :func:`_ensure_loaded` first."""
    global _profile
    if _profile is None:
        # daft-lint: allow(unguarded-global-mutation) -- inside _lock at
        # every call site; the empty-dict install is a benign fallback
        # for callers that skipped _ensure_loaded
        _profile = {}
    return _profile


def _persist(snapshot: Dict[str, Dict[str, float]]) -> None:
    """Atomic profile write (outside the lock: the caller passes a
    snapshot). Best-effort — calibration must never fail a query."""
    path = _path()
    if not path:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"backend": _backend_name(), "ts": time.time(),
                       "entries": snapshot}, f)
        os.replace(tmp, path)
    except OSError:
        pass


def ingest_flight_history(limit: int = 200) -> int:
    """Seed the profile from flight-recorder history
    (``DAFT_TPU_QUERY_LOG``): each persisted query's ``device_kernels``
    block carries per-family achieved bytes/rows/seconds — the same
    evidence ``ledger_record`` observes live, recovered from disk so a
    fresh process starts calibrated. Returns observations ingested."""
    global _history_ingested
    if not enabled() or frozen():
        # do NOT latch: a call while disabled/frozen must not burn the
        # one-time ingest a later enabled process would want
        return 0
    with _lock:
        if _history_ingested:
            return 0
        _history_ingested = True
    try:
        from .. import tracing
        entries = tracing.flight_history(limit=limit)
    except Exception:
        return 0
    n = 0
    for entry in entries:
        dk = entry.get("device_kernels")
        if not isinstance(dk, dict):
            continue
        for kind, d in dk.items():
            if not isinstance(d, dict):
                continue
            try:
                n += _observe_family(
                    kind,
                    rows=float(d.get("rows", 0) or 0),
                    nbytes=float(d.get("bytes", 0) or 0),
                    seconds=float(d.get("seconds", 0) or 0),
                    dispatches=float(d.get("dispatches", 1) or 1))
            except (TypeError, ValueError):
                continue
    return n


# ---------------------------------------------------------------- updates

def observe(name: str, value: float, weight: float = 1.0) -> None:
    """Fold one observed constant sample into the profile (EWMA with an
    effective weight: ``w`` repeated samples collapse to one update).
    No-op when calibration is off or frozen."""
    global _obs_since_persist, _last_persist
    if not enabled() or frozen():
        return
    try:
        value = float(value)
        weight = float(weight)
    except (TypeError, ValueError):
        return
    if not math.isfinite(value) or value <= 0 or weight <= 0:
        return
    global _atexit_registered
    persist_snap = None
    _ensure_loaded()
    with _lock:
        if not _atexit_registered and profile_dir():
            # short-lived processes must not lose the tail of their
            # observations to the persist throttle
            import atexit
            atexit.register(flush)
            _atexit_registered = True
        prof = _load_locked()
        e = prof.get(name)
        if e is None:
            prof[name] = {"value": value, "samples": weight}
        else:
            a = 1.0 - (1.0 - alpha()) ** weight
            e["value"] += a * (value - e["value"])
            e["samples"] += weight
        _obs_since_persist += 1
        now = time.monotonic()
        # BOTH throttles must clear: enough new observations AND a
        # minimum interval elapsed — a hot query must not rewrite the
        # profile file many times per second, and the atexit flush
        # covers whatever a short-lived process accumulates under it
        if _obs_since_persist >= _PERSIST_EVERY \
                and now - _last_persist > _PERSIST_MIN_INTERVAL_S:
            _obs_since_persist = 0
            _last_persist = now
            persist_snap = {k: dict(v) for k, v in prof.items()}
    from ..physical import adaptive
    adaptive.count("calibration_observations")
    if persist_snap is not None:
        _persist(persist_snap)


def flush() -> None:
    """Persist the current profile now (atexit hook / tests / ops)."""
    global _obs_since_persist
    with _lock:
        if _profile is None:
            return
        _obs_since_persist = 0
        snap = {k: dict(v) for k, v in _profile.items()}
    _persist(snap)


_FAMILY_BYTES = {"grouped_agg": "DEV_AGG_BPS",
                 "projection": "DEV_VECTOR_BPS"}
_FAMILY_ROWS = {"argsort": "DEV_SORT_ROWS_PER_S",
                "join": "DEV_JOIN_ROWS_PER_S"}

#: dispatches below these floors measure launch overhead / RTT, not the
#: kernel rate the constants model — skip them
_MIN_OBS_BYTES = 1 << 16
_MIN_OBS_ROWS = 1 << 12
_MIN_OBS_SECONDS = 1e-5


def _observe_family(kind: str, rows: float, nbytes: float, seconds: float,
                    dispatches: float = 1.0) -> int:
    """One ledger-shaped observation → the matching calibrated constant
    (per-dispatch achieved rate, dispatch overhead subtracted so a small
    batch doesn't read as a slow kernel). Returns 1 when recorded."""
    if seconds <= _MIN_OBS_SECONDS or dispatches <= 0:
        return 0
    from . import costmodel
    eff_s = max(seconds - costmodel.DEV_DISPATCH_S * dispatches,
                seconds * 0.1)
    name = _FAMILY_BYTES.get(kind)
    if name is not None and nbytes >= _MIN_OBS_BYTES:
        observe(name, nbytes / eff_s, weight=dispatches)
        return 1
    name = _FAMILY_ROWS.get(kind)
    if name is not None and rows >= _MIN_OBS_ROWS:
        observe(name, rows / eff_s, weight=dispatches)
        return 1
    return 0


def observe_dispatch(kind: str, rows: float, nbytes: float, seconds: float,
                     dispatches: float = 1.0) -> None:
    """Live chokepoint, called by ``costmodel.ledger_record`` at every
    real dispatch. Cheap gate first: the common (calibration-off) path
    is one function call and a dict read."""
    if not enabled():
        return
    _observe_family(kind, rows=rows, nbytes=nbytes, seconds=seconds,
                    dispatches=dispatches)


# ------------------------------------------------------------------ reads

def const(name: str, default: float) -> float:
    """The calibrated value for ``name`` when the profile has one past
    the sample floor (and calibration is on and not frozen); else the
    caller's hard-coded default. This is THE read every costmodel
    decision site routes through. When the local profile is blind a
    gossiped fleet view (sample-weighted over replica origins,
    ``fleet/state_sync``) beats the hard-coded default — this is how a
    cold replica's first query prices like a warm one."""
    if not enabled() or frozen():
        return default
    _ensure_loaded()
    with _lock:
        e = _load_locked().get(name)
        if e is not None and e["samples"] >= min_samples():
            return e["value"]
    # outside _lock: the fleet store has its own lock and must not nest
    # under the profile lock
    fleet = _fleet_const(name)
    return default if fleet is None else fleet


def _fleet_const(name: str) -> Optional[float]:
    """Merged fleet-history value for ``name`` past the sample floor, or
    None when no fleet state store is installed / the fleet is blind."""
    try:
        from ..fleet import state_sync
        st = state_sync.installed()
        if st is None:
            return None
        got = st.merged_calibration(name)
        if got is None:
            return None
        value, samples = got
        if samples < min_samples():
            return None
        state_sync.count("calibration_fleet_reads")
        return float(value)
    except Exception:
        return None


def profile_entries() -> Dict[str, Dict[str, float]]:
    """Copy of the learned profile ``{name: {value, samples}}`` — the
    gossip export consumed by ``fleet/state_sync``."""
    _ensure_loaded()
    with _lock:
        return {k: dict(v) for k, v in _load_locked().items()}


def _quantize(v: float) -> str:
    # 2 significant digits: EWMA nudges within a few percent keep the
    # plan token (and therefore the plan cache) stable
    try:
        return f"{float(v):.1e}"
    except (TypeError, ValueError):
        return "?"


def plan_token() -> str:
    """Calibration-generation token folded into plan fingerprints
    (``logical/fingerprint.py``): a quantized digest of every constant
    ACTIVELY overriding its default right now. When a calibrated value
    crosses the sample floor or moves materially, the token changes and
    cached plans priced under the old constants are invalidated —
    without it, r20's calibrated flips (combine gating, kernel strategy,
    fusion pricing) kept serving stale pre-calibration plans. Empty when
    calibration is off/frozen or nothing is active, so the common path
    leaves fingerprints untouched."""
    if not enabled() or frozen():
        return ""
    floor = min_samples()
    _ensure_loaded()
    with _lock:
        prof = {k: dict(v) for k, v in _load_locked().items()}
    active = {n: _quantize(e["value"]) for n, e in prof.items()
              if e["samples"] >= floor}
    # fleet-inherited constants flip the same decisions local ones do
    try:
        from ..fleet import state_sync
        st = state_sync.installed()
    except Exception:
        st = None
    if st is not None:
        for n, (v, samples) in st.merged_calibration_all().items():
            if n not in active and samples >= floor:
                active[n] = _quantize(v)
    if not active:
        return ""
    import hashlib
    blob = ",".join(f"{n}={active[n]}" for n in sorted(active))
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def ndv_ratio() -> float:
    """Clamped damping factor for parquet-footer NDV evidence (1.0 =
    trust the footer; the observed actual/footer ratio once calibrated)."""
    r = const("NDV_FOOTER_RATIO", 1.0)
    return min(max(r, _NDV_RATIO_MIN), _NDV_RATIO_MAX)


def summary(defaults: Optional[Dict[str, float]] = None
            ) -> Dict[str, Dict[str, object]]:
    """Profile snapshot for explain/tests: per constant the learned
    value, sample count, and whether it is ACTIVE (overriding the
    default) right now."""
    if defaults is None:
        defaults = costmodel_defaults()
    on = enabled() and not frozen()
    floor = min_samples()
    _ensure_loaded()
    with _lock:
        prof = {k: dict(v) for k, v in _load_locked().items()}
    out: Dict[str, Dict[str, object]] = {}
    for name, default in defaults.items():
        e = prof.pop(name, None)
        out[name] = {
            "default": default,
            "value": e["value"] if e else None,
            "samples": e["samples"] if e else 0,
            "active": bool(on and e and e["samples"] >= floor),
        }
    for name, e in prof.items():  # learned names outside the default map
        out[name] = {"default": None, "value": e["value"],
                     "samples": e["samples"],
                     "active": bool(on and e["samples"] >= floor)}
    return out


def costmodel_defaults() -> Dict[str, float]:
    """The hard-coded constants the profile can override, single-sourced
    from the costmodel module attributes."""
    from ..analysis import knobs
    from . import costmodel as cm
    return {
        "DEV_VECTOR_BPS": cm.DEV_VECTOR_BPS,
        "DEV_AGG_BPS": cm.DEV_AGG_BPS,
        "DEV_SORT_ROWS_PER_S": cm.DEV_SORT_ROWS_PER_S,
        "DEV_JOIN_ROWS_PER_S": cm.DEV_JOIN_ROWS_PER_S,
        "SHUFFLE_WIRE_BPS":
            (knobs.REGISTRY["DAFT_TPU_SHUFFLE_WIRE_MBPS"].default or 1000.0)
            * 1e6,
        "ICI_BPS": cm._ICI_FALLBACK_BPS,
        "NDV_FOOTER_RATIO": 1.0,
    }


def calibrated_names() -> list:
    """Names currently overriding their defaults (sorted) — what
    ``explain(analyze=True)`` shows as calibrated-vs-default."""
    return sorted(n for n, d in summary().items() if d["active"])


def reset_for_tests() -> None:
    global _profile, _obs_since_persist, _last_persist, _history_ingested
    with _lock:
        _profile = None
        _obs_since_persist = 0
        _last_persist = 0.0
        _history_ingested = False
