"""AOT session warm-up: compile the device program library before the
first query needs it (``DAFT_TPU_AOT_WARMUP=1``).

ROADMAP item 1's warm-up tax (55s of first-query traces + compiles in
r12) is paid once per (program, size class) — so pay it at session
start, off the query path, and PERSIST it: with the persistent compile
cache on (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.cache/jax``
on a non-CPU backend — ``backend.configure_compile_cache``), every
``jit(...).lower().compile()`` here lands in it, and the next process
re-loads
the executable from disk instead of re-compiling (tracing still runs,
but tracing is milliseconds; compiling was the seconds).  This is the
piece the r11 serving plane's single-flight compile cache needed to
amortize across a fleet: one warm-up populates the shared directory,
every replica reads it.

Two grids, both over the ``column.size_classes`` ladder:

- :func:`warmup_kernels` — the shared device kernel library (argsort,
  grouped-agg, compaction) at representative key layouts;
- :func:`warmup_fragments` — every fused-agg program compiled so far
  (``fragment.fused_programs()``), at the sort strategy and the
  first-dispatch out-cap bucket.  Fragments with data-dependent scalar
  planes (string dictionaries) are skipped and counted: their shapes
  aren't knowable ahead of data.

All compiles run under the ``warmup.aot`` dispatch scope, which the
dispatch registry marks exempt — the retrace sanitizer counts them but
never budget-fails a deliberate warm-up.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

#: default top of the warm-up grid: programs above this capacity are
#: compiled on demand (one trace each, amortized by the same cache)
_DEFAULT_MAX_CAPACITY = 1 << 20
#: ...and default bottom: morsel-sized batches below this are cheap to
#: trace on demand, so the grid starts where compiles start to matter
_DEFAULT_MIN_CAPACITY = 1 << 10


def warmup_kernels(classes: List[int]) -> Dict[str, int]:
    """AOT-compile the shared kernel library over the size-class grid.
    Returns ``{"programs": n, "errors": m}``."""
    import jax

    from ..analysis import retrace_sanitizer
    from . import column as dcol
    from . import kernels
    programs = errors = 0
    fval = np.dtype(np.float64 if dcol.supports_f64() else np.float32)
    for cap in classes:
        k = jax.ShapeDtypeStruct((cap,), np.int64)
        b = jax.ShapeDtypeStruct((cap,), np.bool_)
        v = jax.ShapeDtypeStruct((cap,), fval)
        grid = []
        for nk in (1, 2):
            grid.append(lambda nk=nk: kernels.argsort_kernel.lower(
                (k,) * nk, (b,) * nk, b,
                descending=(False,) * nk,
                nulls_first=(False,) * nk).compile())
        grid.append(lambda: kernels.grouped_agg_kernel.lower(
            (k,), (b,), (v,), (b,), b, ops=("sum",)).compile())
        grid.append(lambda: kernels.compaction_perm.lower(b).compile())
        for fn in grid:
            with retrace_sanitizer.dispatch_scope("warmup.aot",
                                                  ("kernels", cap)):
                try:
                    fn()
                    programs += 1
                except Exception:
                    errors += 1
    return {"programs": programs, "errors": errors}


def warmup_fragments(classes: List[int],
                     progs: Optional[list] = None) -> Dict[str, int]:
    """AOT-compile the fused fragment library over the size classes,
    at the sort strategy (a dense program's ``dims`` are data-shaped).
    Returns program/skip/error counts."""
    import jax

    from ..analysis import retrace_sanitizer
    from . import fragment
    progs = fragment.fused_programs() if progs is None else progs
    programs = skipped = errors = 0
    for prog in progs:
        if prog.in_np_dtypes is None or prog.compiled.scalar_specs:
            skipped += 1   # string-scalar planes are data-shaped
            continue
        for cap in classes:
            arrays = {n: jax.ShapeDtypeStruct((cap,), dt)
                      for n, dt in prog.in_np_dtypes.items()}
            valids = {n: jax.ShapeDtypeStruct((cap,), np.bool_)
                      for n in prog.in_np_dtypes}
            mask = jax.ShapeDtypeStruct((cap,), np.bool_)
            out_cap = min(fragment._OUT_CAP0, cap)
            with retrace_sanitizer.dispatch_scope(
                    "warmup.aot", ("fragment", id(prog), cap, "sort")):
                try:
                    prog.packed_fn.lower(
                        arrays, valids, mask, (),
                        out_cap=out_cap, strategy="sort").compile()
                    programs += 1
                except Exception:
                    errors += 1
    return {"programs": programs, "skipped": skipped, "errors": errors}


def warmup_regions(classes: List[int],
                   progs: Optional[list] = None) -> Dict[str, int]:
    """AOT-compile every fusion-region program seen so far (round 21's
    whole-query compilation library, ``fragment.fused_region_programs()``)
    over the size-class grid at each shape's first-dispatch width rung.
    join_agg regions warm the probe=build diagonal of their 2-D capacity
    grid — off-diagonal pairs compile on demand into the same cache."""
    import jax

    from ..analysis import retrace_sanitizer
    from . import column as dcol
    from . import fragment
    progs = fragment.fused_region_programs() if progs is None else progs
    programs = skipped = errors = 0
    for prog in progs:
        if prog.in_np_dtypes is None:
            skipped += 1
            continue
        is_join = isinstance(prog, fragment.FusedJoinAggProgram)
        if is_join:
            if prog.build_np_dtypes is None or prog.c_post.scalar_specs \
                    or (prog.c_pred is not None
                        and prog.c_pred.scalar_specs):
                skipped += 1
                continue
        elif prog.compiled.scalar_specs:
            skipped += 1   # string-scalar planes are data-shaped
            continue
        for cap in classes:
            arrays = {n: jax.ShapeDtypeStruct((cap,), dt)
                      for n, dt in prog.in_np_dtypes.items()}
            valids = {n: jax.ShapeDtypeStruct((cap,), np.bool_)
                      for n in prog.in_np_dtypes}
            mask = jax.ShapeDtypeStruct((cap,), np.bool_)
            with retrace_sanitizer.dispatch_scope(
                    "warmup.aot", ("region", id(prog), cap)):
                try:
                    if is_join:
                        b_arrays = {n: jax.ShapeDtypeStruct((cap,), dt)
                                    for n, dt
                                    in prog.build_np_dtypes.items()}
                        b_valids = {n: jax.ShapeDtypeStruct(
                            (cap,), np.bool_)
                            for n in prog.build_np_dtypes}
                        b_sorted = jax.ShapeDtypeStruct(
                            (cap,), prog.build_np_dtypes[prog.rkey])
                        b_perm = jax.ShapeDtypeStruct((cap,), np.int32)
                        b_live = jax.ShapeDtypeStruct((), np.int32)
                        prog.packed_fn.lower(
                            arrays, valids, mask, (), b_arrays, b_valids,
                            b_sorted, b_perm, b_live, (), W=cap,
                            out_cap=min(fragment._OUT_CAP0, cap)
                        ).compile()
                    else:
                        if prog.shape == "topk":
                            out_w = min(dcol.bucket_capacity(
                                max(prog.limit, 1)), cap)
                        elif not prog.has_pred:
                            out_w = cap
                        else:
                            out_w = min(dcol.bucket_capacity(
                                max(cap // 4, fragment._OUT_CAP0)), cap)
                        prog.packed_fn.lower(
                            arrays, valids, mask, (),
                            out_w=out_w).compile()
                    programs += 1
                except Exception:
                    errors += 1
    return {"programs": programs, "skipped": skipped, "errors": errors}


def warmup_session(max_capacity: int = _DEFAULT_MAX_CAPACITY,
                   min_capacity: int = _DEFAULT_MIN_CAPACITY,
                   kernels: bool = True,
                   fragments: bool = True,
                   regions: bool = True) -> Dict[str, object]:
    """Run the full warm-up (kernel library + fragment library + fusion
    regions) over the configured size-class ladder; returns a stats
    dict.  Callers gate on ``DAFT_TPU_AOT_WARMUP`` (the serving
    scheduler does at startup)."""
    from . import column as dcol
    t0 = time.perf_counter()
    classes = dcol.size_classes(max_capacity, min_capacity)
    stats: Dict[str, object] = {"size_classes": list(classes)}
    if kernels:
        stats["kernels"] = warmup_kernels(classes)
    if fragments:
        stats["fragments"] = warmup_fragments(classes)
    if regions:
        stats["regions"] = warmup_regions(classes)
    stats["seconds"] = round(time.perf_counter() - t0, 3)
    return stats


def warmup_enabled() -> bool:
    """Env var is the per-process override; unset, the per-query
    ``ExecutionConfig.tpu_aot_warmup`` field applies."""
    from ..analysis import knobs
    if knobs.env_is_set("DAFT_TPU_AOT_WARMUP"):
        return bool(knobs.env_bool("DAFT_TPU_AOT_WARMUP"))
    try:
        from ..context import get_context
        return bool(get_context().execution_config.tpu_aot_warmup)
    except Exception:
        return False


def maybe_warmup_session() -> Optional[Dict[str, object]]:
    """Knob-gated warm-up for session/serving startup; never raises
    (a warm-up failure must not take the serving plane down)."""
    if not warmup_enabled():
        return None
    try:
        return warmup_session()
    except Exception:
        return None
