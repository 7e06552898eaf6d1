"""Measured transfer-aware dispatch cost model.

Round 2's dispatch gate reasoned about *output shape only* ("row-shaped
results never pay for the link"). That heuristic is right on a slow
host↔device link and wrong on a fast one, where row-shaped outputs are
perfectly fine. This module replaces the shape heuristic with the
comparison the reference's per-operator dispatch seam implies (SURVEY.md
§7 hard-part #2):

    device_time = bytes_up/up_bw + bytes_down/down_bw + round_trips·RTT
                  (+ kernel time, usually negligible next to the link terms)
    host_time   = bytes_touched / host_kernel_bandwidth

and runs the op on whichever side is cheaper. The link terms are MEASURED,
not assumed: the first decision on a non-CPU backend calibrates RTT and
both bandwidths (see ``_measure`` — a few tiny round trips plus 8 MiB
transfers, once per process). Host
kernel bandwidths are coarse constants for pyarrow's SIMD kernels — they
only need to be right to an order of magnitude because real decisions are
dominated by the link terms (a 40 MB/s link vs GB/s host, or a PCIe-class
link vs GB/s host).

Env overrides (testing / ops):
- ``DAFT_TPU_LINK_RTT_MS`` / ``DAFT_TPU_LINK_UP_MBPS`` /
  ``DAFT_TPU_LINK_DOWN_MBPS``: skip measurement, use these numbers.
- ``DAFT_TPU_DEVICE_FORCE=1``: the device always wins (existing knob).
"""

from __future__ import annotations

import math
import os
import threading
import time
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from .. import tracing as _tracing

# host-side kernel bandwidths (bytes/s) for the Arrow compute tier these
# decisions compare against; coarse on purpose (see module docstring)
HOST_VECTOR_BPS = 2.0e9     # elementwise eval / filter, per byte touched
HOST_AGG_BPS = 3.0e8        # hash/grouped aggregation, per byte touched
HOST_SORT_ROWS_PER_S = 12.0e6   # multi-key argsort, rows/s
HOST_JOIN_ROWS_PER_S = 10.0e6   # sort-merge match of a bucket pair, rows/s
#                                 (left + right). Read on the chip's host,
#                                 PR 38: 241 M rows of TPC-H SF10 Q3 / Q10
#                                 pairs in 22.9 s of ``joins.match_indices``
#                                 under the pipeline's stage threads (5.9 M
#                                 rows/s under 0.5 M-row pairs, 10.5 M at
#                                 2.1 M-row ones)
HOST_PIL_BPS = 85e6             # per-image PIL resize, input bytes/s
#                                 (measured: 64x64 RGB -> 32x32, 1 core)

# device-side terms: without these a zero-cost link (CPU backend, local
# HBM) degenerates to "device always wins" no matter how slow the kernel
DEV_VECTOR_BPS = 8.0e9      # fused elementwise XLA, per byte touched
DEV_AGG_BPS = 4.0e9         # fused grouped-agg, per byte touched
DEV_SORT_ROWS_PER_S = 50.0e6    # XLA multi-key sort, rows/s
DEV_JOIN_ROWS_PER_S = 2.0e6     # sort/searchsorted/expand join, rows/s
#                                 (left + right). Read on a TPU v5e, PR 38:
#                                 the same 80 pairs a pass, 60.3 M rows, took
#                                 29.5 s of device time as
#                                 ``join_fused_impl`` (device trace, forced
#                                 run). Slower than the host's rate: the gate
#                                 keeps a pair on the host at every size
#                                 until the kernel changes
HOST_SELECT_VALUES_PER_S = 150.0e6  # a filtered Parquet scan in the reader
#                                 (read, decode, filter), values (rows x
#                                 pruned columns) a second of the scan's
#                                 wall under the scan pool. Read on the
#                                 chip's host, PR 41: TPC-H SF10 Q14's
#                                 lineitem, 4 numeric / date columns x 60 M
#                                 rows in 1.3-1.8 s of ``scan:load``
#                                 (136-185 M values/s); Q19's 6 columns, two
#                                 of them strings, read 62-75 M. The host's
#                                 better case: the gate errs to the host
DEV_SELECT_ROWS_PER_S = 300.0e6     # the chain program's part that grows
#                                 with the table (predicate, the running
#                                 count of the mask), padded rows a second
#                                 of device time. Read on a TPU v5e, PR 41:
#                                 2.6 ms (Q14's date range) to ~15 ms
#                                 (Q19's string tests) a 4 194 304-row table
DEV_SELECT_SLOT_S = 0.05e-6         # and its part that grows with the
#                                 survivors' bucket: the block search and
#                                 the output stage (one row gather a plane
#                                 and a dense lane pick) a slot. Read on a
#                                 TPU v5e, PR 42, over a 4 M-row table
#                                 (``chip_proof/compact_bench.py``): search
#                                 4.36 ms + Q19's eight planes 7.16 ms at
#                                 the 262 144 rung (44 ns a slot), 1.19 +
#                                 1.33 ms at Q14's 65 536 (38 ns); in the
#                                 star cell's device trace a whole Q19
#                                 table takes 11.4 ms, predicate included.
#                                 Rounded up, so the gate errs to the host.
#                                 PR 41 read 0.2e-6: twelve element
#                                 gathers a slot, 43 ms of a 46 ms table
SELECT_FETCH_BPS = 0.25e9           # packed survivors back on the host:
#                                 fetch + decode, bytes a second. Read, PR
#                                 41: Q19's 168 MB a query in 0.17 s of link
#                                 (0.98 GB/s down) + 0.5 s of
#                                 ``device:decode``; Q14's 33.5 MB in 0.10 s
DEV_DISPATCH_S = 2.0e-3     # per-decision executable launch + (amortized)
#                             shape-bucket compile overhead
INVEST_MAX_RATIO = 8.0      # max cache-fill cost vs one host pass (see
#                             agg_upload_wins' bounded-investment rule).
#                             Sized to realistic reuse: a TPC-H suite pass
#                             re-touches a hot column ~3-6×, so a fill
#                             costing more than ~8 host passes cannot repay
#                             within a workload; 64 (r4) let 20-30× fills
#                             through on slow-link days, which one-shot
#                             suites never amortized


@dataclass(frozen=True)
class LinkProfile:
    rtt_s: float
    up_bps: float
    down_bps: float

    def device_seconds(self, bytes_up: float, bytes_down: float,
                       round_trips: float, kernel_s: float = 0.0) -> float:
        return (bytes_up / self.up_bps + bytes_down / self.down_bps
                + round_trips * self.rtt_s + kernel_s)

    def pipelined_seconds(self, bytes_up: float, bytes_down: float,
                          round_trips: float, kernel_s: float = 0.0
                          ) -> float:
        """Steady-state per-morsel cost with the async device pipeline
        (round 17) overlapping the transfer legs with neighbor morsels'
        compute: the bottleneck stage sets throughput, so the effective
        cost is the slower of (wire time, kernel time) plus one RTT for
        the dispatch tail — never more than the serial chain.  The
        serial model charged full upload+download+RTT per morsel, which
        made the strategy ladder under-dispatch to the device exactly
        when overlap would hide the transfer."""
        link_s = bytes_up / self.up_bps + bytes_down / self.down_bps
        serial = link_s + round_trips * self.rtt_s + kernel_s
        steady = max(link_s, kernel_s) + self.rtt_s
        return min(serial, steady)


_SHARED_MEMORY = LinkProfile(0.0, math.inf, math.inf)

_lock = threading.Lock()
_profile: Optional[LinkProfile] = None


def _cal(name: str, default: float) -> float:
    """Read one costmodel constant through the calibration store (round
    20): the learned per-backend value once its sample floor is met and
    ``DAFT_TPU_CALIBRATION`` is on; the hard-coded default otherwise
    (and always under the chaos-determinism freeze)."""
    from . import calibration
    return calibration.const(name, default)


def _env_profile() -> Optional[LinkProfile]:
    from ..analysis import knobs
    rtt = knobs.env_float("DAFT_TPU_LINK_RTT_MS", default=None)
    up = knobs.env_float("DAFT_TPU_LINK_UP_MBPS", default=None)
    down = knobs.env_float("DAFT_TPU_LINK_DOWN_MBPS", default=None)
    if rtt is None and up is None and down is None:
        return None
    return LinkProfile(
        rtt_s=(rtt if rtt is not None else 1.0) / 1e3,
        up_bps=(up if up is not None else 100.0) * 1e6,
        down_bps=(down if down is not None else 100.0) * 1e6)


def _measure() -> LinkProfile:
    """One-time link calibration: 4 tiny round trips plus two timed 8 MiB
    one-way legs per round, two rounds (seconds on a ~10-40 MB/s link,
    far less on a local chip; paid once per boot — see the persisted
    profile in ``link_profile``).

    Robustness notes: the FIRST tiny round trip pays lazy-init costs
    (~10-20× a steady-state RTT) — warm up and take the median of three.
    ``block_until_ready`` after ``jnp.asarray`` does not reliably reflect
    wire time for uploads (staged copies), and a cold timed pass would
    absorb XLA compile time — so an UNTIMED pass compiles + stages first,
    then the upload rate comes from a verified round trip (upload, force a
    kernel, fetch) minus the separately measured download time. Two
    rounds, and the SLOWER one wins: single 8 MiB samples over-reported a
    slow link by 2-10× in r4, and an optimistic link estimate buys
    expensive device mispredicts (Q22: +8.8 s at SF10) while a
    pessimistic one merely leaves the op on the host."""
    import statistics

    import jax
    import jax.numpy as jnp

    tiny = np.zeros(8, dtype=np.float32)
    jax.device_get(jnp.asarray(tiny))  # warmup: lazy init paid here
    rtts = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.device_get(jnp.asarray(tiny))
        rtts.append(time.perf_counter() - t0)
    rtt = max(statistics.median(rtts), 1e-7)

    nbytes = 1 << 23  # 8 MiB
    big = np.zeros(nbytes // 4, dtype=np.float32)
    # untimed first pass: compiles the +0 executable AND leaves the data
    # resident, so the timed rounds below measure pure wire time
    dev = jnp.asarray(big) + 0
    dev.block_until_ready()
    down_best, up_best = None, None
    for rnd in range(2):
        t0 = time.perf_counter()
        jax.device_get(dev)
        down_s = max(time.perf_counter() - t0 - rtt / 2, 1e-7)
        # verified round trip (compile-cached): upload + fetch. NB: must
        # use a FRESH buffer — jax dedupes transfers of the same numpy
        # object, which would make the upload leg look free
        big2 = big + (1.0 + rnd)
        t0 = time.perf_counter()
        jax.device_get(jnp.asarray(big2) + 0)
        round_s = time.perf_counter() - t0
        # a sane floor: the upload leg of an 8 MiB round cannot beat 10×
        # the measured download rate even on asymmetric links
        up_s = max(round_s - down_s - rtt, down_s / 10, 1e-7)
        # keep the SLOWER (conservative) of the rounds
        down_best = down_s if down_best is None else max(down_best, down_s)
        up_best = up_s if up_best is None else max(up_best, up_s)
    return LinkProfile(rtt_s=rtt,
                       up_bps=nbytes / up_best,
                       down_bps=nbytes / down_best)


_LINK_CACHE_TTL_S = 1800.0   # reuse a stored profile this long
_LINK_BLEND_MAX_S = 6 * 3600.0  # blend with a stale profile up to this age


def _link_cache_path() -> str:
    from ..analysis import knobs
    p = knobs.env_str("DAFT_TPU_LINK_CACHE_PATH")
    if p:
        return p
    # inside the checkout (git-ignored), like the compile cache: state
    # from outside the checkout must not steer dispatch
    from . import backend
    return os.path.join(backend.cache_root(), "link_profile.json")


def _load_stored(backend_name: str):
    """(LinkProfile, age_s) from the persisted cache, or (None, None)."""
    import json
    try:
        with open(_link_cache_path()) as f:
            d = json.load(f)
        if d.get("backend") != backend_name:
            return None, None
        age = time.time() - float(d["ts"])
        return LinkProfile(rtt_s=float(d["rtt_s"]),
                           up_bps=float(d["up_bps"]),
                           down_bps=float(d["down_bps"])), age
    except (OSError, ValueError, KeyError, TypeError, AttributeError):
        return None, None


def _store(backend_name: str, p: LinkProfile) -> None:
    import json
    path = _link_cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"backend": backend_name, "ts": time.time(),
                       "rtt_s": p.rtt_s, "up_bps": p.up_bps,
                       "down_bps": p.down_bps}, f)
        os.replace(tmp, path)
    except OSError:
        pass


def link_profile() -> LinkProfile:
    """The measured (or overridden) host↔device link profile. CPU backends
    share host memory: zero-cost link.

    Non-CPU profiles persist across processes (``<repo>/.cache/
    link_profile.json``, ``DAFT_TPU_LINK_CACHE_PATH`` to move,
    ``DAFT_TPU_LINK_CACHE=0`` to disable): re-measuring every process
    costs seconds on a slow link AND made dispatch decisions flip-flop
    between processes when a single noisy sample landed on the other side
    of a threshold (r4 postmortem). Within the TTL the stored profile is
    used as-is; after it, a fresh measurement is geometric-blended with
    the stored one (if not too stale) to damp sample noise. A measurement
    that THROWS on an accelerator raises: a chip that cannot move 8 MiB
    is broken, and an assumed slow link would quietly keep every query on
    the host."""
    global _profile
    if _profile is not None:
        return _profile
    with _lock:
        if _profile is not None:
            return _profile
        env = _env_profile()
        if env is not None:
            _profile = env
            return _profile
        from . import backend
        bname = backend.backend_name() or "cpu"
        if bname == "cpu":
            _profile = _SHARED_MEMORY
            return _profile
        from ..analysis import knobs
        use_cache = bool(knobs.env_bool("DAFT_TPU_LINK_CACHE"))
        # daft-lint: allow(blocking-under-lock) -- intentional: _lock held
        # across load/measure/store so threads wait for the ONE calibration
        # instead of racing duplicate multi-second link measurements
        stored, age = _load_stored(bname) if use_cache else (None, None)
        if stored is not None and age is not None and age < _LINK_CACHE_TTL_S:
            _profile = stored
            return _profile
        meas = _measure()
        if stored is not None and age is not None \
                and age < _LINK_BLEND_MAX_S:
            meas = LinkProfile(
                rtt_s=math.sqrt(meas.rtt_s * stored.rtt_s),
                up_bps=math.sqrt(meas.up_bps * stored.up_bps),
                down_bps=math.sqrt(meas.down_bps * stored.down_bps))
        if use_cache:
            # daft-lint: allow(blocking-under-lock) -- tiny atomic JSON
            # write, same single-calibration critical section as above
            _store(bname, meas)
        _profile = meas
        return _profile


def reset_for_tests() -> None:
    global _profile, _ici
    with _lock:
        _profile = None
    with _ici_lock:
        _ici = None
    decision_counts.clear()
    for k in scan_table_counts:
        scan_table_counts[k] = 0
    ledger_reset()
    _peaks_memo.clear()
    from . import calibration
    calibration.reset_for_tests()


# ------------------------------------------------------ silicon peak specs

#: Published peaks of ONE chip, keyed by the ``device_kind`` JAX reports.
#: Source: Google Cloud documentation, "TPU v5e" (system architecture):
#: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip. JAX names that chip
#: ``TPU v5 lite`` (read off the attached chip, PR 23). A device that is
#: not in this table — the CPU included — has NO peaks: the ledger then
#: omits ``roofline_pct`` / ``mfu_pct`` instead of printing a share of a
#: chip that is not attached. Add a chip by adding its row, with its source.
DEVICE_PEAKS: dict = {
    "TPU v5 lite": {"peak_flops": 197e12, "hbm_bps": 819e9},
}

_peaks_memo: list = []


def device_peaks() -> Optional[dict]:
    """``{"peak_flops", "hbm_bps"}`` of the attached chip, or None when
    the backend is the CPU, unavailable, or a device kind not in
    :data:`DEVICE_PEAKS`. Memoized (the device cannot change under us)."""
    if _peaks_memo:
        return _peaks_memo[0]
    from . import backend
    name = backend.backend_name()
    if name is None:
        return None  # still probing / failed: do not memoize
    peaks = None
    if name != "cpu":
        import jax
        peaks = DEVICE_PEAKS.get(jax.devices()[0].device_kind)
    _peaks_memo.append(peaks)
    return peaks


def peak_flops() -> Optional[float]:
    """The attached chip's peak FLOP/s (bf16-class), or None (no peaks)."""
    p = device_peaks()
    return p["peak_flops"] if p else None


def hbm_bps() -> Optional[float]:
    """The attached chip's HBM bandwidth (bytes/s), or None (no peaks)."""
    p = device_peaks()
    return p["hbm_bps"] if p else None


def pct_of_peak(per_second: float, peak: Optional[float],
                digits: int = 4) -> Optional[float]:
    """``100 * per_second / peak`` rounded, or None without a peak."""
    return round(100.0 * per_second / peak, digits) if peak else None


# ------------------------------------------------- per-dispatch MFU ledger

#: achieved-work accounting per kernel family, recorded at every REAL
#: dispatch site (argsort / join / grouped_agg / projection …) — not the
#: synthetic microbenchmarks. ``mfu.report()`` embeds a snapshot so bench
#: artifacts carry the per-dispatch evidence behind any efficiency claim.
kernel_ledger: dict = {}
_ledger_lock = threading.Lock()

_LEDGER_RAW = ("dispatches", "rows", "bytes", "flops", "seconds")
#: strategy accounting: per-family sort/dense dispatch counts — the
#: per-query stats block derives `strategy` from these.  ``serial_s``
#: (round 17) is the serial-equivalent stage seconds the async pipeline
#: measured against its pipelined wall — the overlap evidence.
_LEDGER_STRATEGY = ("strategy_sort", "strategy_dense", "serial_s",
                    "fused_ops", "rt_saved", "fusion_serial_s")


def ledger_record(kind: str, *, rows: int = 0, nbytes: float = 0.0,
                  flops: float = 0.0, seconds: float = 0.0,
                  dispatches: int = 1, strategy: Optional[str] = None,
                  serial_seconds: Optional[float] = None,
                  fused_ops: Optional[int] = None,
                  round_trips_saved: Optional[int] = None,
                  fusion_serial_seconds: Optional[float] = None) -> None:
    """Record one real dispatch's achieved work.

    ``seconds`` is wall time from dispatch to host-visible result — it
    includes host↔device link time, so the derived utilization is a
    LOWER bound on silicon utilization (the synthetic ``mfu.report``
    isolates the silicon with in-jit repetition). ``nbytes``/``flops``
    are the kernel's modeled HBM traffic / arithmetic, conservative.
    ``strategy`` (``sort``/``dense``) lands in the same family row for
    the stats block. The ``region`` family (round 21) additionally carries
    ``fused_ops`` (operators compiled into the region programs),
    ``round_trips_saved`` (host round-trips the fusion eliminated vs the
    per-fragment chain), and ``fusion_serial_seconds`` — the modeled
    serial per-fragment equivalent, from which the stats block derives
    the ``fusion_x`` ratio the way ``serial_seconds`` yields
    ``overlap_x``."""
    fields = [("dispatches", dispatches), ("rows", rows),
              ("bytes", float(nbytes)), ("flops", float(flops)),
              ("seconds", float(seconds))]
    if strategy in ("sort", "dense"):
        fields.append((f"strategy_{strategy}", dispatches))
    if serial_seconds is not None:
        fields.append(("serial_s", float(serial_seconds)))
    if fused_ops is not None:
        fields.append(("fused_ops", int(fused_ops)))
    if round_trips_saved is not None:
        fields.append(("rt_saved", int(round_trips_saved)))
    if fusion_serial_seconds is not None:
        fields.append(("fusion_serial_s", float(fusion_serial_seconds)))
    with _ledger_lock:
        d = kernel_ledger.setdefault(
            kind, {k: 0 if k in ("dispatches", "rows") else 0.0
                   for k in _LEDGER_RAW})
        for f, v in fields:
            d[f] = d.get(f, 0) + v
    # outside the ledger lock: also credit the thread-attributed stats
    # context (concurrent queries must not read each other's dispatches
    # out of the shared ledger diff)
    from .. import observability as obs
    for field, v in fields:
        if v:
            obs.bump_plane("device_kernels", f"{kind}\x00{field}", v)
    # calibration chokepoint (round 20): every real dispatch's achieved
    # rate feeds the learned cost-model profile (no-op unless
    # DAFT_TPU_CALIBRATION is on and the chaos freeze is off)
    from . import calibration
    calibration.observe_dispatch(kind, rows=rows, nbytes=nbytes,
                                 seconds=seconds, dispatches=dispatches)
    # tracing plane: one span per real dispatch, carrying the ledger's
    # roofline story onto the query timeline (guard-checked: untraced
    # queries build nothing here)
    from .. import tracing
    tctx = tracing.current()
    if tctx is not None:
        attrs = {"rows": rows, "bytes": int(nbytes), "flops": int(flops)}
        if strategy:
            attrs["strategy"] = strategy
        if seconds > 0:
            attrs["gbps"] = round(nbytes / seconds / 1e9, 3)
            for key, rate, peak in (
                    ("roofline_pct", nbytes / seconds, hbm_bps()),
                    ("mfu_pct", flops / seconds, peak_flops())):
                if rate and peak:
                    attrs[key] = pct_of_peak(rate, peak)
        dur_us = int(seconds * 1e6)
        rec = tctx.recorder
        rec.add(f"device:{kind}",
                rec.unique_span_id(f"device:{kind}"), tctx.span_id,
                tracing._now_us() - dur_us, dur_us, attrs=attrs,
                lane="device")


def _derive(d: dict) -> dict:
    out = {k: (round(v, 6) if isinstance(v, float) else v)
           for k, v in d.items() if k not in _LEDGER_STRATEGY}
    s = d.get("seconds", 0.0)
    if s > 0 and d.get("bytes"):
        out["achieved_gbps"] = round(d["bytes"] / s / 1e9, 3)
        pct = pct_of_peak(d["bytes"] / s, hbm_bps())
        if pct is not None:  # a known chip is attached (DEVICE_PEAKS)
            out["roofline_pct"] = pct
    if s > 0:
        if d.get("flops"):
            out["achieved_tflops"] = round(d["flops"] / s / 1e12, 4)
            pct = pct_of_peak(d["flops"] / s, peak_flops())
            if pct is not None:
                out["mfu_pct"] = pct
    counts = {nm: int(d.get(f"strategy_{nm}", 0))
              for nm in ("sort", "dense")}
    ran = [nm for nm, c in counts.items() if c]
    if ran:
        out["strategy"] = ran[0] if len(ran) == 1 else "mixed"
        if len(ran) > 1:
            for nm in ran:
                out[f"strategy_{nm}"] = counts[nm]
    ser = d.get("serial_s", 0.0)
    if ser and s > 0:
        # round 17 overlap evidence: serial-equivalent stage seconds vs
        # the pipelined wall — >1.0 means the async window really hid
        # host encode/decode + transfer behind device compute
        out["serial_equiv_s"] = round(ser, 6)
        out["overlap_x"] = round(ser / s, 3)
    if d.get("fused_ops"):
        out["fused_ops"] = int(d["fused_ops"])
    if d.get("rt_saved"):
        out["round_trips_saved"] = int(d["rt_saved"])
    fser = d.get("fusion_serial_s", 0.0)
    if fser and s > 0:
        # round 21 fusion evidence: modeled serial per-fragment seconds
        # vs the fused-region wall — >1.0 means compiling the chain into
        # one program really beat dispatching it operator-at-a-time
        out["fusion_serial_s"] = round(fser, 6)
        out["fusion_x"] = round(fser / s, 3)
    return out


def ledger_snapshot(raw: bool = False) -> dict:
    """Per-family sums; with derived GB/s + roofline/MFU percentages
    unless ``raw`` (raw snapshots are what ``ledger_delta`` diffs)."""
    with _ledger_lock:
        snap = {k: dict(v) for k, v in kernel_ledger.items()}
    if raw:
        return snap
    out = {k: _derive(d) for k, d in snap.items()}
    fails = failures_snapshot()
    if fails:
        # not a kernel family: device work that failed and ran on the
        # host instead (runtime.device_failed) — never silently absent
        out["device_failures"] = fails
    return out


def ledger_delta(before: dict, after: dict) -> dict:
    """Derived ledger for the work BETWEEN two raw snapshots (per-query
    accounting in observability)."""
    out = {}
    for kind, d in after.items():
        b = before.get(kind, {})
        diff = {k: d.get(k, 0) - b.get(k, 0)
                for k in _LEDGER_RAW + _LEDGER_STRATEGY}
        if diff["dispatches"] > 0:
            out[kind] = _derive(diff)
    return out


def ledger_from_tallies(flat: dict) -> dict:
    """Derived per-kind ledger from a context-attributed flat tally
    (``"<kind>\\x00<field>"`` keys, the shape ``ledger_record`` bumps into
    a RuntimeStatsContext plane) — same output shape as ``ledger_delta``."""
    kinds: dict = {}
    for key, v in flat.items():
        kind, _, field = key.partition("\x00")
        if field not in _LEDGER_RAW + _LEDGER_STRATEGY:
            continue
        d = kinds.setdefault(
            kind, {k: 0 if k in ("dispatches", "rows") else 0.0
                   for k in _LEDGER_RAW})
        d[field] = int(v) if field in ("dispatches", "rows") else float(v)
    return {k: _derive(d) for k, d in kinds.items()
            if d["dispatches"] > 0}


# ------------------------------------------- device failures (PR 23)

#: per-site record of device work that FAILED and was replaced by a host
#: run: ``{site: {"count": n, "first_error": text}}``. Written only by
#: ``runtime.device_failed`` (the one helper every degrade-to-host catch
#: goes through); a healthy process keeps this empty.
device_failures: dict = {}


def failure_record(site: str, text: str) -> bool:
    """Count one degraded device failure; True on the site's first."""
    with _ledger_lock:
        d = device_failures.get(site)
        if d is None:
            device_failures[site] = {"count": 1, "first_error": text}
            return True
        d["count"] += 1
        return False


def failures_snapshot() -> dict:
    with _ledger_lock:
        return {k: dict(v) for k, v in device_failures.items()}


def ledger_reset() -> None:
    with _ledger_lock:
        kernel_ledger.clear()
        device_failures.clear()


def _forced() -> Optional[bool]:
    from ..analysis import knobs
    v = knobs.env_raw("DAFT_TPU_DEVICE_FORCE")
    if v is None:
        return None
    # spellings documented in the knob registry: 1/device force device,
    # 0/host force host
    if v.lower() in ("1", "device", "on", "true"):
        return True
    if v.lower() in ("0", "host", "off", "false"):
        return False
    return None


# ------------------------------------------------------- decision logging

#: in-process decision counters {kind: {"device": n, "host": n}} — surfaced
#: by explain_analyze; reset_for_tests clears them
decision_counts: dict = {}
_counts_lock = threading.Lock()

#: where each scan task's table came from on the device tier's scan path
#: (``executor._fragment_scan_tasks``), since the process started: served
#: from the HBM column cache, encoded and uploaded now, or left to the
#: host. A table from the cache passes no gate, so ``decision_counts``
#: never sees it; reset_for_tests zeroes these
scan_table_counts: dict = dict.fromkeys(_tracing.TABLE_SOURCES, 0)


def count_scan_table(source: str, chip: Optional[int] = None,
                     rows: int = 0) -> None:
    """Tally one scan task's table, process-wide and on the current
    query's trace (its root span and ``summary()["tables"]``). A table
    that runs on the device is also tallied, with its rows, under the
    chip that holds it (``summary()["chips"]``; ``chip`` None is the
    default device, chip 0)."""
    with _counts_lock:
        scan_table_counts[source] += 1
    _tracing.tally(source)
    if source != "host":
        _tracing.tally_chip(chip or 0, tables=1, rows=rows)


def note_resident_chips(n_chips: int) -> None:
    """On the current query's trace, the HBM column cache's bytes on each
    of the ``n_chips`` chips as they stand now (a chip that holds
    nothing reads 0). No-op when untraced."""
    if _tracing.current() is None:
        return
    from . import cache
    held = cache.get_cache().stats()["chips"]
    for chip in range(n_chips):
        _tracing.tally_chip(
            chip, resident_bytes=held.get(chip, {}).get("bytes", 0))


def _log(kind: str, device: bool, host_s: float, dev_s: float,
         **extras) -> None:
    """Record one dispatch decision. Always counts in-process; additionally
    appends a JSONL record when ``DAFT_TPU_DISPATCH_LOG`` names a file —
    the raw material for regressing predicted-vs-actual residuals (r4:
    per-query mispredicts like Q22-at-SF10 could only be diagnosed by
    re-deriving which decisions each query made)."""
    from ..analysis import knobs
    path = knobs.env_str("DAFT_TPU_DISPATCH_LOG")
    rec = None
    if path:
        import json
        rec = {"kind": kind, "device": bool(device),
               "host_s": round(host_s, 6), "dev_s": round(dev_s, 6)}
        rec.update({k: (round(v, 6) if isinstance(v, float) else v)
                    for k, v in extras.items()})
        rec = json.dumps(rec) + "\n"
    with _counts_lock:
        d = decision_counts.setdefault(kind, {"device": 0, "host": 0})
        d["device" if device else "host"] += 1
        if rec is None:
            return
        # the JSONL append stays under the SAME lock: concurrent executor
        # threads must not interleave partial lines (single small O_APPEND
        # writes are usually atomic on Linux, but that is not guaranteed,
        # and the handle is reopened per record)
        try:
            # daft-lint: allow(blocking-under-lock) -- the serialization IS
            # the point (see comment above); sub-ms local append
            with open(path, "a") as f:
                f.write(rec)
        except OSError:
            pass


# ---------------------------------------------------------------- decisions

def row_output_op_wins(bytes_up: float, bytes_down: float,
                       round_trips: float = 2.0,
                       host_bytes: Optional[float] = None) -> bool:
    """Projection / predicate / similar: output is row-shaped; host cost is
    a vector pass over the touched bytes. ``bytes_up`` is wire (encoded)
    bytes; ``host_bytes`` the raw Arrow bytes a host pass touches
    (defaults to ``bytes_up``)."""
    f = _forced()
    if f is not None:
        return f
    host_s = ((host_bytes if host_bytes is not None else bytes_up)
              + bytes_down) / HOST_VECTOR_BPS
    kernel_s = DEV_DISPATCH_S + (bytes_up + bytes_down) \
        / _cal("DEV_VECTOR_BPS", DEV_VECTOR_BPS)
    dev_s = link_profile().device_seconds(
        bytes_up, bytes_down, round_trips, kernel_s)
    _log("row_output", dev_s < host_s, host_s, dev_s,
         bytes_up=bytes_up, bytes_down=bytes_down)
    return dev_s < host_s


def image_resize_wins(bytes_up: float, bytes_down: float) -> bool:
    """Batched device image resize vs per-image PIL. The host alternative
    is PIL's scalar loop (~85 MB/s single-core), far slower than a SIMD
    vector pass — so on a local chip the batch wins by orders of
    magnitude, while on a slow link the transfer dominates and PIL
    keeps the work (r4: the ungated device path shipped 50 MB per batch
    over a ~10 MB/s link, 6× slower than host end to end)."""
    f = _forced()
    if f is not None:
        return f
    host_s = bytes_up / HOST_PIL_BPS
    kernel_s = DEV_DISPATCH_S + (bytes_up + bytes_down) \
        / _cal("DEV_VECTOR_BPS", DEV_VECTOR_BPS)
    dev_s = link_profile().device_seconds(bytes_up, bytes_down, 2.0,
                                          kernel_s)
    _log("image_resize", dev_s < host_s, host_s, dev_s,
         bytes_up=bytes_up, bytes_down=bytes_down)
    return dev_s < host_s


def argsort_wins(n_rows: int, key_bytes: float, n_keys: int) -> bool:
    f = _forced()
    if f is not None:
        return f
    host_s = n_rows * max(n_keys, 1) / HOST_SORT_ROWS_PER_S
    bytes_down = n_rows * 8  # the permutation
    kernel_s = DEV_DISPATCH_S + n_rows * max(n_keys, 1) \
        / _cal("DEV_SORT_ROWS_PER_S", DEV_SORT_ROWS_PER_S)
    dev_s = link_profile().device_seconds(key_bytes, bytes_down, 2.0,
                                          kernel_s)
    _log("argsort", dev_s < host_s, host_s, dev_s,
         n_rows=n_rows, key_bytes=key_bytes)
    return dev_s < host_s


def agg_upload_wins(bytes_up: float, bytes_down: float,
                    cacheable: bool, round_trips: float = 2.0,
                    host_bytes: Optional[float] = None,
                    window: int = 1) -> bool:
    """Aggregation whose inputs are NOT already device-resident.

    ``bytes_up`` is the WIRE cost (encoded device bytes: f64 rides f32,
    strings ride i32 codes); ``host_bytes`` is what a host pass actually
    touches (raw Arrow bytes — defaults to ``bytes_up`` for callers that
    only know one number). Conflating them double-counted f64-heavy
    uploads while under-counting the host pass.

    Cacheable inputs (stable scan-task fingerprint, fits the HBM budget) are
    an *investment*: buffer-pool semantics — you don't refuse to fill the
    cache because the fill run is slower than one host query; you fill
    because every later query over the same scan runs resident (one packed
    transfer, ~10× under the host tier measured on Q1/Q6). Opt out with
    ``DAFT_TPU_CACHE_INVEST=0`` for strict one-shot workloads, where the
    upload must beat the host outright.

    Non-cacheable inputs pay full freight against a host pass at
    ``HOST_AGG_BPS`` over the touched bytes.

    The investment is BOUNDED (r4: TPC-H Q22's tiny per-task aggregates
    were 'invested' at ~20x a host pass — 16 RTT-dominated round trips
    the cache never paid back, 10.9s vs 2.1s host on the SF10 suite): a
    fill may cost up to ``INVEST_MAX_RATIO``x the host pass, enough to
    absorb genuinely profitable cache fills (Q1/Q6 measured ~8-9x fill
    for ~10x steady-state) while rejecting fills that would need dozens
    of repeat queries to break even."""
    f = _forced()
    if f is not None:
        return f
    lp = link_profile()
    host_s = (host_bytes if host_bytes is not None else bytes_up) \
        / HOST_AGG_BPS
    kernel_s = DEV_DISPATCH_S + bytes_up / _cal("DEV_AGG_BPS", DEV_AGG_BPS)
    # round 17: with the async pipeline active (window ≥ 2 in-flight
    # morsel slots) the transfer legs overlap neighbor morsels' compute,
    # so the dispatch is priced at the steady-state bottleneck instead
    # of the full serial chain — the serial price under-dispatched to
    # the device exactly when overlap would have hidden the transfer
    dev_s = lp.pipelined_seconds(bytes_up, bytes_down, round_trips,
                                 kernel_s) if window >= 2 else \
        lp.device_seconds(bytes_up, bytes_down, round_trips, kernel_s)
    from ..analysis import knobs
    if cacheable and knobs.env_bool("DAFT_TPU_CACHE_INVEST"):
        # invest only when residency PAYS: a resident rerun (no upload,
        # but every dispatch still pays its — window-amortized, see
        # _fragment_scan_tasks' single packed fetch — round trips) must
        # beat the host pass, else the cache can never repay the fill no
        # matter how many times the query repeats (r4: TPC-H Q22's tiny
        # per-task aggregates burned 10.9s vs 2.1s host at SF10). The
        # ratio bound additionally rejects pathological fill costs.
        resident_s = lp.pipelined_seconds(0.0, bytes_down, round_trips,
                                          kernel_s) if window >= 2 else \
            lp.device_seconds(0.0, bytes_down, round_trips, kernel_s)
        win = resident_s < host_s and dev_s < INVEST_MAX_RATIO * host_s
        _log("agg_upload_invest", win, host_s, dev_s,
             resident_s=resident_s, bytes_up=bytes_up,
             bytes_down=bytes_down, round_trips=round_trips)
        return win
    _log("agg_upload", dev_s < host_s, host_s, dev_s,
         bytes_up=bytes_up, bytes_down=bytes_down, round_trips=round_trips)
    return dev_s < host_s


def fusion_serial_estimate(rows: int, n_ops: int) -> float:
    """Modeled wall of the PER-FRAGMENT serial chain a fused region
    replaced: each of the ``n_ops`` fused operators would have paid its
    own dispatch + transfer legs + round trips. Recorded per dispatch
    into the ``region`` ledger family, where ``_derive`` turns it into
    the ``fusion_x`` ratio (modeled serial / achieved fused wall)."""
    lp = link_profile()
    b = max(rows, 1) * 8.0
    per_op = lp.device_seconds(
        b, b, 2.0,
        DEV_DISPATCH_S + b / _cal("DEV_VECTOR_BPS", DEV_VECTOR_BPS))
    return max(n_ops, 1) * per_op


def fusion_wins(shape: str, rows: int, bytes_up: float, bytes_down: float,
                n_ops: int, host_bytes: Optional[float] = None,
                window: int = 1) -> bool:
    """Admission gate for one FusedRegion morsel (round 21): the single
    fused dispatch — one upload, one kernel, one packed download — against
    the host running the region's whole operator chain. Shapes price the
    host side differently: a chain is ``n_ops`` vectorized passes, a topk
    adds the host sort, a join_agg is the hash join plus the aggregation
    pass. ``DAFT_TPU_FUSION=1`` bypasses this gate entirely (the executor
    force-admits); ``auto`` calls it per morsel."""
    f = _forced()
    if f is not None:
        return f
    lp = link_profile()
    hb = host_bytes if host_bytes is not None else bytes_up
    if shape == "join_agg":
        host_s = rows / HOST_JOIN_ROWS_PER_S + hb / HOST_AGG_BPS
        kernel_s = DEV_DISPATCH_S \
            + rows / _cal("DEV_JOIN_ROWS_PER_S", DEV_JOIN_ROWS_PER_S) \
            + bytes_up / _cal("DEV_AGG_BPS", DEV_AGG_BPS)
    elif shape == "topk":
        host_s = hb / HOST_VECTOR_BPS * max(n_ops - 1, 1) \
            + rows / HOST_SORT_ROWS_PER_S
        kernel_s = DEV_DISPATCH_S \
            + rows / _cal("DEV_SORT_ROWS_PER_S", DEV_SORT_ROWS_PER_S) \
            + bytes_up / _cal("DEV_VECTOR_BPS", DEV_VECTOR_BPS)
    else:
        host_s = hb / HOST_VECTOR_BPS * max(n_ops, 1)
        kernel_s = DEV_DISPATCH_S \
            + bytes_up / _cal("DEV_VECTOR_BPS", DEV_VECTOR_BPS)
    dev_s = lp.pipelined_seconds(bytes_up, bytes_down, 2.0, kernel_s) \
        if window >= 2 else \
        lp.device_seconds(bytes_up, bytes_down, 2.0, kernel_s)
    _log("fusion", dev_s < host_s, host_s, dev_s,
         shape=shape, rows=rows, n_ops=n_ops)
    return dev_s < host_s


# distributed-shuffle wire model: the DCN-tier host↔host transport
# (flight/HTTP shuffle service), NOT the host↔device link profiled above.
# Coarse constants in the same spirit as the host kernel bandwidths — the
# decision only needs the ratio between an agg pass and a row's full
# shuffle trip (serialize + wire + deserialize + reduce-side agg) to the
# right order of magnitude. DAFT_TPU_SHUFFLE_WIRE_MBPS overrides for real
# pod DCN numbers.
SHUFFLE_SER_BPS = 2.0e9   # arrow IPC write/read, per side, per byte


def shuffle_wire_bps() -> float:
    """Wire bandwidth the shuffle/exchange decisions price against. An
    EXPLICIT env setting wins (ops know their DCN); otherwise the
    calibrated rate — observed at every sizable shuffle fetch — beats
    the hard-coded 1000 MB/s default once its sample floor is met."""
    from ..analysis import knobs
    if knobs.env_raw("DAFT_TPU_SHUFFLE_WIRE_MBPS") is not None:
        return knobs.env_float("DAFT_TPU_SHUFFLE_WIRE_MBPS") * 1e6
    return _cal("SHUFFLE_WIRE_BPS",
                knobs.env_float("DAFT_TPU_SHUFFLE_WIRE_MBPS") * 1e6)


# ----------------------------------------------- ICI (mesh) link model
# The third link tier: intra-mesh collective bandwidth (ICI on a pod,
# shared memory on the virtual CPU mesh). MEASURED like the host↔device
# link: one warm timed all_to_all repartition over the process mesh, once
# per process, memoized — the effective rate includes the collective
# kernel's own bucketing work, which is exactly what an exchanged byte
# pays. DAFT_TPU_ICI_MBPS skips measurement (ops / tests / real pod
# numbers); measurement failure falls back to a conservative constant.

MESH_DISPATCH_S = 3e-3     # collective dispatch + amortized per-size-class
#                            compile (programs are memoized per shape
#                            bucket, so the trace cost spreads across
#                            every same-class exchange)
HOST_EXCHANGE_BPS = 6.0e8  # host hash-partition pass (hash + scatter),
#                            per byte — between the vector and agg rates
_ICI_FALLBACK_BPS = 2.0e9  # can't measure → assume a modest link
_ICI_PROBE_ROWS = 1 << 14  # per-shard probe rows (i64 planes)

_ici_lock = threading.Lock()
_ici: Optional[float] = None


def _measure_ici() -> float:
    """MARGINAL collective-exchange bandwidth: two warm timed
    ``sharded_hash_repartition`` probes (the very program the collective
    exchange path dispatches) at 1× and 4× the probe size; the rate comes
    from the byte and time DIFFERENCES, so the fixed dispatch overhead —
    which ``MESH_DISPATCH_S`` models separately — doesn't masquerade as
    link slowness (a single-size probe on the CPU mesh under-reported the
    link ~10× because one small dispatch is overhead-dominated)."""
    import jax

    from ..parallel import exchange, mesh as pmesh
    mesh = pmesh.get_mesh()
    n = pmesh.mesh_size()
    if mesh is None or n < 2:
        raise RuntimeError("no multi-device mesh to calibrate against")

    def timed(rows_per_shard: int):
        total = n * rows_per_shard
        plane = np.arange(total, dtype=np.int64)
        valid = np.ones(total, dtype=bool)
        pid = (np.arange(total) % n).astype(np.int32)

        def run():
            sb = lambda a: exchange.shard_blocks(mesh, a)
            out = exchange.sharded_hash_repartition(
                mesh, (sb(plane),), (sb(valid),), sb(valid), sb(pid))
            jax.block_until_ready(out)

        run()  # warm-up: compile + stage paid here, not in the timed pass
        t0 = time.perf_counter()
        run()
        # full exchanged payload: value plane + valid + row mask + pid
        return time.perf_counter() - t0, total * (8 + 1 + 1 + 4)

    t1, b1 = timed(_ICI_PROBE_ROWS)
    t2, b2 = timed(4 * _ICI_PROBE_ROWS)
    if t2 > t1:
        return (b2 - b1) / (t2 - t1)
    return b2 / max(t2, 1e-7)  # noisy clock: effective rate of the big probe


def ici_bps() -> float:
    """The calibrated (or overridden) intra-mesh collective bandwidth,
    bytes/s."""
    global _ici
    if _ici is not None:
        return _ici
    with _ici_lock:
        if _ici is not None:
            return _ici
        from ..analysis import knobs
        env = knobs.env_float("DAFT_TPU_ICI_MBPS", default=None)
        if env is not None:
            _ici = env * 1e6
            return _ici
        measured = None
        try:
            # daft-lint: allow(blocking-under-lock) -- intentional: one
            # calibration per process; concurrent deciders wait for it
            # instead of racing duplicate mesh probes
            measured = _measure_ici()
            _ici = measured
        except Exception:
            # can't probe this process → the calibrated (cross-process)
            # rate beats the hard-coded fallback once it has samples
            _ici = _cal("ICI_BPS", _ICI_FALLBACK_BPS)
    if measured is not None:
        # outside the probe lock: fold only a REAL measurement into the
        # persisted per-backend profile (feeding the fallback constant
        # back in would let it masquerade as evidence) so meshless
        # processes start calibrated
        from . import calibration
        calibration.observe("ICI_BPS", measured)
    return _ici


def mesh_exchange_wins(rows: Optional[int], row_bytes: float = 32.0,
                       n_shards: int = 2) -> bool:
    """Admission for a LOCAL mesh collective (DeviceExchangeAgg, the
    in-process hash repartition): price the collective — dispatch +
    amortized compile + the bytes over the calibrated ICI rate — against
    one host hash-partition pass over the same bytes. Replaces the static
    64Ki-row gate, which measured rows and ignored row width: a 50k-row
    200-byte-row exchange was wrongly declined while a 100k-row 8-byte
    one was wrongly accepted on a slow mesh. Unknown ``rows`` keeps the
    old optimistic behavior (the structural gates already vetted the
    plan). ``DAFT_TPU_MESH_MIN_ROWS`` (when set) force-overrides in
    ``parallel/mesh.py`` before this is consulted."""
    if rows is None:
        return True
    if rows <= 0:
        return False
    nbytes = rows * max(row_bytes, 1.0)
    host_s = nbytes / HOST_EXCHANGE_BPS
    dev_s = MESH_DISPATCH_S + nbytes / ici_bps()
    _log("mesh_exchange", dev_s < host_s, host_s, dev_s,
         rows=rows, row_bytes=row_bytes, n_shards=n_shards)
    return dev_s < host_s


def exchange_collective_wins(rows: Optional[int],
                             row_bytes: float = 32.0) -> bool:
    """Price a DISTRIBUTED hash boundary's collective path against the
    Flight wire: the collective pays one mesh dispatch plus the bytes
    over ICI; the Flight trip pays IPC serialize + wire + deserialize per
    byte. With no cardinality evidence the collective wins by default —
    an intra-mesh boundary riding the wire is the pathology this decision
    exists to stop, and the runtime admission gate
    (``mesh.mesh_admits``) re-checks with exact rows before dispatching
    the program. Logged under ``exchange_path`` ("device" = collective
    family)."""
    if not rows:
        _log("exchange_path", True, 0.0, 0.0, rows=rows or 0)
        return True
    nbytes = rows * max(row_bytes, 1.0)
    wire_s = nbytes * (2.0 / SHUFFLE_SER_BPS + 1.0 / shuffle_wire_bps())
    coll_s = MESH_DISPATCH_S + nbytes / ici_bps()
    _log("exchange_path", coll_s < wire_s, wire_s, coll_s,
         rows=rows, row_bytes=row_bytes)
    return coll_s < wire_s


def shuffle_combine_wins(rows: Optional[int], groups: Optional[int],
                         num_partitions: int, n_cols: int = 4,
                         bytes_per_col: float = 8.0,
                         exact_groups: bool = False) -> bool:
    """Price the map-side shuffle combine for a hash boundary feeding a
    decomposable grouped aggregation (Partial Partial Aggregates).

    The combine pays one extra grouped-agg pass over the map output
    (``rows`` state rows at ``HOST_AGG_BPS``) and saves the full shuffle
    trip — IPC serialize, wire, deserialize, reduce-side agg — for every
    row it eliminates: without the combine the wire carries ~``rows``
    per-morsel group states, with it at most ``groups × num_partitions``
    (each map task holds ≤ groups states per partition). Near-unique keys
    (TPC-H Q18's shape) eliminate almost nothing and decline; reductive
    group-bys (Q1's shape) accept.

    With no cardinality evidence the combine wins by default — for
    decomposable aggs the pre-shuffle combine is the literature's default,
    and its worst case (zero reduction) costs one extra linear pass while
    its best saves the whole wire. The decision lands in
    ``decision_counts``/the dispatch log under ``shuffle_combine``
    ("device" = combine applied)."""
    row_bytes = max(n_cols, 1) * bytes_per_col
    if not rows or not groups:
        # no cardinality evidence: default-accept, logged like every
        # other decision so the combine is always traceable
        _log("shuffle_combine", True, 0.0, 0.0, rows=rows or 0,
             groups=groups or 0, num_partitions=num_partitions)
        return True
    if not exact_groups:
        # round 20: footer NDV evidence is damped by the calibrated
        # actual/footer ratio — parquet min/max range NDV systematically
        # over-predicts (a sparse key set reads as near-unique), which
        # declined combines that would have collapsed the wire. EXACT
        # evidence (measured by the re-planner) is never damped.
        from . import calibration
        groups = max(groups * calibration.ndv_ratio(), 1.0)
    groups_out = min(rows, groups * max(num_partitions, 1))
    saved_rows = max(rows - groups_out, 0)
    per_byte_trip = (2.0 / SHUFFLE_SER_BPS + 1.0 / shuffle_wire_bps()
                     + 1.0 / HOST_AGG_BPS)
    saved_s = saved_rows * row_bytes * per_byte_trip
    extra_s = rows * row_bytes / HOST_AGG_BPS
    _log("shuffle_combine", saved_s > extra_s, extra_s, saved_s,
         rows=rows, groups=groups, num_partitions=num_partitions)
    return saved_s > extra_s


def combine_wins_pure(rows: Optional[int], groups: Optional[int],
                      num_partitions: int, n_cols: int = 4,
                      bytes_per_col: float = 8.0) -> bool:
    """The HARD-CODED combine decision — same math as
    ``shuffle_combine_wins`` but with no calibration damping, no
    logging, and no side effects. The runtime re-planner compares the
    evidence-priced decision against this to count ``combine_flips``
    without double-tallying ``decision_counts``."""
    if not rows or not groups:
        return True
    row_bytes = max(n_cols, 1) * bytes_per_col
    groups_out = min(rows, groups * max(num_partitions, 1))
    saved_rows = max(rows - groups_out, 0)
    per_byte_trip = (2.0 / SHUFFLE_SER_BPS + 1.0 / shuffle_wire_bps()
                     + 1.0 / HOST_AGG_BPS)
    return saved_rows * row_bytes * per_byte_trip \
        > rows * row_bytes / HOST_AGG_BPS


# --------------------------------------------- out-of-core spill pricing

SPILL_DISK_BPS = 1.5e9   # spill-tier IPC write/read rate, per byte per
#                          direction (local NVMe with lz4 buffer
#                          compression; coarse like the host constants —
#                          the decision only needs the ratio of one extra
#                          disk round trip to an in-memory pass)


def spill_plan_wins(nbytes: float, resident_budget: float) -> bool:
    """Price a spill-partitioned plan (grace join pairwise phase /
    spill-partitioned agg) against the in-memory single-unit plan for
    ``nbytes`` of materialized input with ``resident_budget`` bytes
    allowed resident.

    A spilled partition is a price, not a failure (HiFrames): past the
    resident budget the in-memory plan is INFEASIBLE (an OOM has
    infinite cost) and the partitioned plan wins outright; under it the
    partitioned plan pays one extra IPC write+read of the overflow it
    would have spilled — zero when everything stayed resident — so small
    inputs keep the whole-input single join/merge. Logged under
    ``spill_plan`` ("device" = partitioned plan chosen).

    Pressure-aware (r23): under governor memory pressure the resident
    budget this decision prices against halves — a gather that fits on
    paper is still the wrong plan when the PROCESS is already at its
    high watermark, so borderline inputs flip to the partitioned plan
    early. Inert when the governor is (no limit / chaos freeze)."""
    try:
        from ..execution import governor
        scale = governor.budget_scale()
        if scale != 1.0:
            resident_budget = resident_budget * scale
    except Exception:
        pass
    agg_s = nbytes / HOST_AGG_BPS
    if nbytes > resident_budget:
        part_s = agg_s + 2.0 * (nbytes - resident_budget) / SPILL_DISK_BPS
        _log("spill_plan", True, 1e12, part_s,
             nbytes=nbytes, budget=resident_budget)
        return True
    # everything fits resident: the partitioned plan would spill nothing
    # but still forfeits the whole-input kernel pass — in-memory wins
    _log("spill_plan", False, agg_s, agg_s,
         nbytes=nbytes, budget=resident_budget)
    return False


def join_wins(n_left: int, n_right: int, bytes_up: float,
              bytes_down: float, window: int = 1) -> bool:
    """Equi-join as one fused device program (sort/searchsorted/expand):
    output is one packed index matrix; host cost is a hash build+probe.
    ONE dispatch and ONE result transfer (the r5 three-phase pipeline
    paid 3 dispatches + 4 round trips)."""
    f = _forced()
    if f is not None:
        return f
    n = n_left + n_right
    host_s = n / HOST_JOIN_ROWS_PER_S
    kernel_s = DEV_DISPATCH_S \
        + n / _cal("DEV_JOIN_ROWS_PER_S", DEV_JOIN_ROWS_PER_S)
    lp = link_profile()
    # round 17: overlap pricing when the async pipeline is active (the
    # join's upload/download legs hide behind neighbor dispatches)
    dev_s = lp.pipelined_seconds(bytes_up, bytes_down, 2.0, kernel_s) \
        if window >= 2 else \
        lp.device_seconds(bytes_up, bytes_down, 2.0, kernel_s)
    _log("join", dev_s < host_s, host_s, dev_s,
         n_left=n_left, n_right=n_right, bytes_up=bytes_up)
    return dev_s < host_s


def select_wins(rows: int, n_cols: int, slots: Optional[int],
                out_words: int, resident: bool, bytes_up: float = 0.0,
                cacheable: bool = False) -> bool:
    """A filtered scan's table that ends in rows: the chain program over
    its encoded columns (one dispatch, the packed survivors back) against
    the host's reader (read, decode and filter the ``n_cols`` pruned
    columns of ``rows`` rows). ``slots`` is the bucket the expected
    survivors fill, ``out_words`` the packed words a slot; None: no table
    of this predicate has run and the footer bounds nothing, so the host
    takes it and the bet is made on what it finds.

    A resident table is priced from read rates alone, so its decision
    does not move with the probed link. A miss reads the same columns
    unfiltered, encodes and uploads them (the link's price, as for an
    aggregate's upload) and is an investment under the same two rules as
    ``agg_upload_wins``: the resident rerun must beat the host, and the
    fill may cost at most ``INVEST_MAX_RATIO`` host passes."""
    f = _forced()
    if f is not None:
        return f
    host_s, fixed_s, slot_s = _select_prices(rows, n_cols, out_words)
    if slots is None:
        _log("select", False, host_s, math.inf, rows=rows,
             resident=resident)
        return False
    resident_s = fixed_s + slots * slot_s
    if resident:
        _log("select", resident_s < host_s, host_s, resident_s, rows=rows,
             slots=slots, resident=True)
        return resident_s < host_s
    from ..analysis import knobs
    dev_s = host_s + link_profile().device_seconds(bytes_up, 0.0, 1.0,
                                                   resident_s)
    win = cacheable and knobs.env_bool("DAFT_TPU_CACHE_INVEST") \
        and resident_s < host_s and dev_s < INVEST_MAX_RATIO * host_s
    _log("select_invest", win, host_s, dev_s, resident_s=resident_s,
         rows=rows, bytes_up=bytes_up, slots=slots)
    return win


def _select_prices(rows: int, n_cols: int, out_words: int
                   ) -> Tuple[float, float, float]:
    """(the reader's seconds for a table; the resident selection's seconds
    whatever survives: one dispatch and the program's pass over the rows;
    its seconds a slot of the survivors' bucket: the search, the output
    stage's row gathers, fetch and decode)."""
    return (rows * max(n_cols, 1) / HOST_SELECT_VALUES_PER_S,
            DEV_DISPATCH_S + rows / DEV_SELECT_ROWS_PER_S,
            DEV_SELECT_SLOT_S + 8.0 * out_words / SELECT_FETCH_BPS)


def select_max_rows(rows: int, n_cols: int, out_words: int) -> int:
    """The most survivors of a ``rows``-row table worth fetching: where
    the resident selection's price meets the host's (the ladder's
    ceiling; a table past it is re-read on the host)."""
    host_s, fixed_s, slot_s = _select_prices(rows, n_cols, out_words)
    return max(int((host_s - fixed_s) / slot_s), 0)


def count_select(tier: str, tables: int, rows_in: int,
                 rows_out: int, row_gather: int = 0) -> None:
    """Tally filtered scan tables that ended in rows, by where their
    filter ran (``device`` / ``host``), with the rows they held and the
    rows that survived (and how many of the device's tables the program
    answered by row gathers), on the current query's trace
    (``summary()["selects"]``)."""
    _tracing.tally(f"select_tables_{tier}", tables)
    _tracing.tally("select_rows_in", rows_in)
    _tracing.tally("select_rows_out", rows_out)
    if tier == "device":    # what the programs read and the fetch carries
        _tracing.tally("select_tables_row_gather", row_gather)
        _tracing.tally("select_rows_in_device", rows_in)
        _tracing.tally("select_rows_out_device", rows_out)


# ------------------------------------------------------ kernel strategy

def log_strategy_decision(kind: str, strategy: str, **extras) -> None:
    """Tally the grouped-aggregate strategy (``dense`` / ``sort``) a
    dispatch site really ran, once a dispatch. The tally lands on the
    "host" side of ``decision_counts``: its "device" side stood for a
    third strategy that is gone, and the benchmark's
    ``device_decisions_pct`` sums every kind, so moving the tally would
    move that metric."""
    _log(kind, False, 0.0, 0.0, strategy=strategy, **extras)
