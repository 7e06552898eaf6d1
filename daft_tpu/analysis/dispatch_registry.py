"""The engine's declarative jit/Pallas dispatch-site registry.

Every place the engine *constructs* a ``jax.jit`` program or a
``pallas_call`` is declared here exactly once: which module, which
enclosing function, what the trace signature is allowed to depend on,
and how many traces one signature may legitimately cost.  Two consumers
keep the table honest:

- ``rule_shapes`` (static): any jit/pallas construction site in the
  engine tree that is NOT declared here is a finding
  (``dispatch-site-unregistered``), and any declared site that no longer
  exists is one too (``dispatch-site-stale``) — the registry can neither
  under- nor over-claim.
- ``retrace_sanitizer`` (runtime): dispatch chokepoints enter a
  ``dispatch_scope(site_id, signature_key)`` around the jitted call;
  JAX trace events that fire inside the scope are charged against the
  site's declared per-signature budget, and exceeding it fails the test
  session (``DAFT_TPU_SANITIZE=1`` + ``DAFT_TPU_SANITIZE_RETRACE``).

The budget contract is the shape-discipline invariant of ROADMAP item 1
stated declaratively: *a dispatch site re-traces only when its declared
signature changes* — e.g. the fused fragment traces once per
(program, capacity class, out-cap bucket, strategy, donation,
scalar-plane shapes), never per raw row count.  Row counts must reach
shapes only through the ``column.bucket_capacity`` size-class
chokepoint, which ``rule_shapes``' taint rule enforces statically.

This module must stay import-light (dataclasses only): the lint rules
AND the runtime sanitizer both import it.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

#: kwarg-ish module qualifier for sites living at module level
MODULE_LEVEL = "<module>"


@dataclasses.dataclass(frozen=True)
class DispatchSite:
    id: str          # stable site id ("fragment.packed", …)
    module: str      # repo-relative path of the constructing module
    funcs: Tuple[str, ...]  # enclosing function names of the jit/pallas
    # construction site(s); MODULE_LEVEL for top-level decorators/wraps
    signature: str   # what the trace cache key may depend on (doc + the
    # contract the runtime scope key must spell)
    budget: str      # human budget contract for the docs table
    traces_per_key: int = 1   # max traces one signature key may cost
    exempt: bool = False      # bench/warm-up sites that TIME compiles on
    # purpose: counted, never budget-enforced
    memo: str = "local"       # who owns the program memo: "local" (the
    # constructing function must store it — rule_shapes enforces the
    # _mask_cache pattern) or "caller" (the construction is returned
    # and the CALLERS hold the cache, e.g. compile_projection →
    # runtime._projection_cache / fragment._fused_cache)


def _s(id_, module, funcs, signature, budget, traces_per_key=1,
       exempt=False, memo="local"):
    return DispatchSite(id_, module, tuple(funcs), signature, budget,
                        traces_per_key, exempt, memo)


SITES: Tuple[DispatchSite, ...] = (
    # ------------------------------------------------------ device tier
    _s("kernels.argsort", "daft_tpu/device/kernels.py",
       (MODULE_LEVEL,),
       "(n_keys, key dtypes, capacity class, descending, nulls_first)",
       "one trace per key-plane layout x size class"),
    _s("kernels.grouped_agg", "daft_tpu/device/kernels.py",
       (MODULE_LEVEL,),
       "(n_keys, n_vals, dtypes, ops, capacity class, out_cap bucket)",
       "one trace per agg layout x size class x out-cap bucket"),
    _s("kernels.join_fused", "daft_tpu/device/kernels.py",
       ("join_fused_kernel",),
       "(capacity classes, out_capacity bucket, donate)",
       "one trace per build/probe size class x out bucket"),
    _s("fragment.packed", "daft_tpu/device/fragment.py",
       ("get_fused_agg",),
       "(program, capacity class, out_cap bucket, strategy, donate, "
       "scalar-plane shapes, the table's chip)",
       "one trace per (schema, size-class, strategy), not per row count"),
    _s("fragment.donate", "daft_tpu/device/fragment.py",
       ("donate_fn",),
       "(program, capacity class, out_cap bucket, strategy, "
       "scalar-plane shapes, the table's chip)",
       "donating twin of fragment.packed; same signature contract"),
    _s("fragment.round", "daft_tpu/device/fragment.py",
       ("round_fn",),
       "(program, capacity class, out_cap bucket, strategy, dims, "
       "scalar-plane shapes, the chips of the round)",
       "SPMD twin of fragment.packed over the data mesh, a table a chip: "
       "one trace per (schema, size-class, strategy, chips)"),
    _s("region.chain", "daft_tpu/device/fragment.py",
       ("get_fused_region",),
       "(program, capacity class, out-width bucket, scalar-plane shapes)",
       "round 21 fused chain region: one trace per (region program, "
       "size class, transfer-width bucket), never per row count"),
    _s("region.topk", "daft_tpu/device/fragment.py",
       ("get_fused_region",),
       "(program, capacity class, k bucket, scalar-plane shapes)",
       "round 21 fused top-k region: one trace per (region program, "
       "size class, k bucket)"),
    _s("region.join_agg", "daft_tpu/device/fragment.py",
       ("get_fused_join_agg",),
       "(program, probe capacity class, build capacity class, pair-width "
       "bucket W, out_cap bucket, scalar-plane shapes)",
       "round 21 fused join_agg region: one trace per (region program, "
       "probe/build size classes, W bucket, group bucket)"),
    _s("region.build", "daft_tpu/device/fragment.py",
       ("prepare_region_build",),
       "(build capacity class,)",
       "join_agg build-side key sort: one trace per build size class, "
       "reused by every probe morsel of every query"),
    _s("pipeline.mask", "daft_tpu/device/pipeline.py",
       ("_masked_validity",),
       "(validity-plane capacity class,)",
       "one trace per capacity class (live count rides as a traced "
       "scalar, never a literal)"),
    _s("compiler.projection", "daft_tpu/device/compiler.py",
       ("compile_projection",),
       "(expression keys, schema, capacity class, scalar-plane shapes)",
       "one trace per compiled projection x size class (memoized by "
       "callers: runtime._projection_cache / fragment._fused_cache)",
       memo="caller"),
    _s("mfu.bench", "daft_tpu/device/mfu.py",
       ("measure_grouped_agg", "measure_join", "measure_argsort"),
       "(bench shape grid)",
       "roofline harness: re-times compiles on purpose", exempt=True),
    # warmup.aot constructs no programs of its own — it .lower()s the
    # sites above over the size-class grid — so it claims no
    # construction functions, only a scope id the sanitizer exempts
    _s("warmup.aot", "daft_tpu/device/warmup.py", (),
       "(size-class x strategy warm-up grid)",
       "AOT warm-up: every lower().compile() here is deliberate",
       exempt=True),
    # ----------------------------------------------------- parallel tier
    _s("exchange.shard_map", "daft_tpu/parallel/exchange.py",
       ("shard_map_compat",),
       "(mapped fn code + closure, mesh, in_specs, out_specs, "
       "check_vma, input plane shapes)",
       "one trace per collective program x shard block shape (memoized "
       "in _program_cache)"),
    # ------------------------------------------------------- functions
    _s("image.resize", "daft_tpu/functions/image.py",
       ("_get_resize_jit",),
       "(batch shape, target h/w, clip bounds, out dtype)",
       "one trace per image batch shape x resize spec"),
)

BY_ID: Dict[str, DispatchSite] = {s.id: s for s in SITES}

#: module → allowed enclosing-function names (rule_shapes' coverage map)
MODULE_FUNCS: Dict[str, set] = {}
for _site in SITES:
    MODULE_FUNCS.setdefault(_site.module, set()).update(_site.funcs)


def site(site_id: str) -> Optional[DispatchSite]:
    return BY_ID.get(site_id)


def memo_owner(module: str, func: str) -> Optional[str]:
    """``"local"``/``"caller"`` for a declared (module, enclosing-func)
    construction site, ``"exempt"`` for bench/warm-up sites, or None
    when the site is undeclared (rule_shapes flags those separately)."""
    for s in SITES:
        if s.module == module and func in s.funcs:
            return "exempt" if s.exempt else s.memo
    return None


def budget_for(site_id: str) -> Optional[int]:
    """Max traces per signature key, or None when the site is exempt
    (bench/warm-up) or unknown (unscoped engine traces are counted but
    never budget-enforced)."""
    s = BY_ID.get(site_id)
    if s is None or s.exempt:
        return None
    return s.traces_per_key
