"""Rule family 4 — jit hygiene for the device tier.

The whole value of ``daft_tpu/device`` is *statically provable* dispatch
behavior (PR 1): the packed-key argsort compiles to ≤3 ``lax.sort``
operands for ANY key count, and the fused join runs build+probe+expand
as ONE jit program with no host round-trips between phases. Two ways to
silently lose that:

- host side effects inside a jit'd kernel (``print``/``open``/env
  reads) — they fire at trace time, not run time, and mask retracing;
- ``np.*`` math on traced values — numpy silently forces the tracer to
  concretize (a hidden device→host transfer per call), or fails only on
  the real accelerator. Trace-time ``np`` on *static* metadata (dtypes,
  shapes, pack plans) is the kernel idiom and stays allowed; the rule
  taints function parameters and flags value-computing ``np.*`` calls
  whose arguments derive from them.

Static rules: ``host-effect-in-jit``, ``np-in-jit``.

Contract re-verification (``check_dispatch_contracts``): rebuilds the
jaxprs and re-proves PR 1's numbers — ``dispatch-contract`` findings on
violation. The jaxpr-walking helpers here (:func:`max_sort_operands`,
:func:`count_primitive`, the ``*_jaxpr`` builders) are the single
source tests use too (``tests/test_device_kernels.py``).
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from .framework import Finding, SourceFile, call_name

KERNELS_PATH = "daft_tpu/device/kernels.py"

#: np attributes that are trace-time metadata, not value math
_NP_STATIC_OK = {
    "dtype", "iinfo", "finfo", "result_type", "promote_types", "can_cast",
    "issubdtype", "ndim", "shape", "ceil", "floor", "log2",
    "int8", "int16", "int32", "int64", "uint8", "uint16", "uint32",
    "uint64", "float16", "float32", "float64", "bool_",
}

_HOST_EFFECTS = {"print", "open", "input", "breakpoint"}
_HOST_EFFECT_PREFIXES = ("os.environ", "os.getenv", "time.", "sys.std")


def _jit_function_names(tree: ast.Module) -> Set[str]:
    """Names of functions that end up inside ``jax.jit`` — via decorator
    (``@jax.jit`` / ``@partial(jax.jit, …)``) or wrap-site
    (``jax.jit(f, …)`` / ``partial(jax.jit, …)(f)``)."""
    jitted: Set[str] = set()

    def _dotted(node):
        from .framework import dotted_name
        return dotted_name(node)

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _dotted(dec) in ("jax.jit", "jit"):
                    jitted.add(node.name)
                elif isinstance(dec, ast.Call):
                    name = call_name(dec)
                    if name in ("jax.jit", "jit"):
                        jitted.add(node.name)
                    elif name.endswith("partial") and dec.args \
                            and _dotted(dec.args[0]) in ("jax.jit", "jit"):
                        jitted.add(node.name)
        elif isinstance(node, ast.Call):
            name = call_name(node)
            if name in ("jax.jit", "jit"):
                if node.args and isinstance(node.args[0], ast.Name):
                    jitted.add(node.args[0].id)
            elif isinstance(node.func, ast.Call):
                inner = node.func
                if call_name(inner).endswith("partial") and inner.args \
                        and _dotted(inner.args[0]) in ("jax.jit", "jit"):
                    if node.args and isinstance(node.args[0], ast.Name):
                        jitted.add(node.args[0].id)
    return jitted


def _param_names(fn) -> Set[str]:
    names = set()
    for node in ast.walk(fn):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            a = node.args
            for arg in (a.posonlyargs + a.args + a.kwonlyargs):
                names.add(arg.arg)
            if a.vararg:
                names.add(a.vararg.arg)
            if a.kwarg:
                names.add(a.kwarg.arg)
    return names


def _taint(fn) -> Set[str]:
    """Names (transitively) derived from the function's parameters —
    fixpoint over assignments, order-insensitive."""
    tainted = _param_names(fn)
    for _ in range(6):
        grew = False
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                value_names = {n.id for n in ast.walk(node.value)
                               if isinstance(n, ast.Name)}
                if value_names & tainted:
                    for tgt in node.targets:
                        for n in ast.walk(tgt):
                            if isinstance(n, ast.Name) \
                                    and n.id not in tainted:
                                tainted.add(n.id)
                                grew = True
            elif isinstance(node, (ast.For, ast.comprehension)):
                it = node.iter
                value_names = {n.id for n in ast.walk(it)
                               if isinstance(n, ast.Name)}
                if value_names & tainted:
                    tgt = node.target
                    for n in ast.walk(tgt):
                        if isinstance(n, ast.Name) and n.id not in tainted:
                            tainted.add(n.id)
                            grew = True
        if not grew:
            break
    return tainted


def check(sources: List[SourceFile]) -> List[Finding]:
    out: List[Finding] = []
    for sf in sources:
        if not sf.path.startswith("daft_tpu/device/"):
            continue
        jitted = _jit_function_names(sf.tree)
        if not jitted:
            continue
        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name in jitted:
                out.extend(_check_jit_body(sf, node))
    return out


def _check_jit_body(sf: SourceFile, fn) -> List[Finding]:
    out = []
    tainted = _taint(fn)
    from .framework import dotted_name
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        if name in _HOST_EFFECTS \
                or any(name.startswith(p) for p in _HOST_EFFECT_PREFIXES):
            out.append(Finding(
                "host-effect-in-jit", sf.path, node.lineno,
                f"{name}() inside jit'd kernel {fn.name}() — fires at "
                f"trace time, not dispatch time"))
            continue
        parts = name.split(".")
        if len(parts) == 2 and parts[0] in ("np", "numpy") \
                and parts[1] not in _NP_STATIC_OK:
            arg_names = set()
            for a in list(node.args) + [kw.value for kw in node.keywords]:
                for n in ast.walk(a):
                    if isinstance(n, ast.Name):
                        arg_names.add(n.id)
            if arg_names & tainted:
                out.append(Finding(
                    "np-in-jit", sf.path, node.lineno,
                    f"{name}() applied to traced value(s) "
                    f"({', '.join(sorted(arg_names & tainted))}) inside "
                    f"jit'd kernel {fn.name}() — forces host concretization; "
                    f"use jnp or mark static"))
    return out


# ---------------------------------------------------- dispatch contracts

#: the committed kernel contracts (PR 1): single source for the lint
#: runner and tests/test_device_kernels.py
ARGSORT_MAX_SORT_OPERANDS = 3
ARGSORT_CASES = ((1, "int64"), (2, "float32"), (3, "int64"),
                 (6, "int32"), (8, "float32"))
FORBIDDEN_IN_FUSED_JOIN = ("pure_callback", "io_callback",
                           "debug_callback", "callback")


def max_sort_operands(jaxpr) -> int:
    """Deepest ``lax.sort`` operand count anywhere in a (closed) jaxpr."""
    mx = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            mx = max(mx, len(eqn.invars))
        for sub in eqn.params.values():
            if hasattr(sub, "jaxpr"):
                mx = max(mx, max_sort_operands(sub.jaxpr))
    return mx


def count_primitive(jaxpr, name: str) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            n += 1
        for sub in eqn.params.values():
            if hasattr(sub, "jaxpr"):
                n += count_primitive(sub.jaxpr, name)
    return n


def argsort_jaxpr(n_keys: int, dtype: str = "int64"):
    import jax
    import numpy as np
    from ..device import kernels as K
    C = 32
    keys = tuple(np.arange(C, dtype=dtype) for _ in range(n_keys))
    valids = tuple(np.ones(C, bool) for _ in range(n_keys))
    mask = np.ones(C, bool)
    flags = tuple(False for _ in range(n_keys))
    return jax.make_jaxpr(lambda ks, vs, m: K.argsort_kernel(
        ks, vs, m, flags, flags))(keys, valids, mask)


def grouped_agg_jaxpr(n_keys: int = 5):
    import jax
    import numpy as np
    from ..device import kernels as K
    C = 32
    keys = tuple(np.arange(C, dtype=np.int64) for _ in range(n_keys))
    ones = tuple(np.ones(C, bool) for _ in range(n_keys))
    mask = np.ones(C, bool)
    vals = (np.ones(C, np.float32),)
    return jax.make_jaxpr(
        lambda ks, kv, v, vv, m: K.grouped_agg_block_impl(
            ks, kv, v, vv, m, ("sum",), 16))(keys, ones, vals, (mask,), mask)


def join_fused_jaxpr(capacity: int = 64):
    import jax
    import numpy as np
    from ..device import kernels as K
    C = 32
    key = np.arange(C, dtype=np.int64)
    ones = np.ones(C, bool)
    return jax.make_jaxpr(
        lambda lk, lv, lm, rk, rv, rm: K.join_fused_impl(
            lk, lv, lm, rk, rv, rm, capacity))(
        key, ones, ones, key, ones, ones)


FRAGMENT_PATH = "daft_tpu/device/fragment.py"
#: round 21's whole-query compilation contract: a fusion region is ONE
#: jit program — its fresh jaxpr carries ZERO host-callback primitives
#: (an in-region callback would be a hidden host round-trip, the exact
#: thing fusion exists to eliminate), every lax.sort inside stays within
#: the ≤3-operand packed-code budget, and each region dispatch site is
#: declared in the registry with a finite per-signature trace budget.
REGION_SITES = ("region.chain", "region.topk", "region.join_agg",
                "region.build")


def _region_chain_jaxpr(topk: bool = False):
    """Fresh jaxpr of a representative chain/topk region program."""
    import jax
    import numpy as np
    from .. import col
    from ..schema import DataType, Field, Schema
    from ..device import fragment as F
    schema = Schema([Field("a", DataType.int64()),
                     Field("b", DataType.float64())])
    exprs = [(col("b") * 2.0).alias("b2"), col("a")]
    pred = col("a") > 10
    if topk:
        prog = F.get_fused_region(exprs, pred, schema,
                                  sort_by=(col("b"),), descending=(True,),
                                  nulls_first=(False,), limit=8,
                                  fused_ops=("Filter", "Project", "TopN"))
    else:
        prog = F.get_fused_region(exprs, pred, schema,
                                  fused_ops=("Filter", "Project"))
    if prog is None:
        raise RuntimeError("representative region program did not lower")
    C = 64
    arrays = {"a": np.arange(C, dtype=np.int64),
              "b": np.ones(C, np.float64)}
    valids = {"a": np.ones(C, bool), "b": np.ones(C, bool)}
    mask = np.ones(C, bool)
    return jax.make_jaxpr(lambda ar, va, m: prog._run_packed(
        ar, va, m, (), out_w=32))(arrays, valids, mask)


def _region_join_agg_jaxpr():
    """Fresh jaxpr of a representative join_agg region program."""
    import jax
    import numpy as np
    from .. import col
    from ..schema import DataType, Field, Schema
    from ..device import fragment as F
    src = Schema([Field("k", DataType.int64()),
                  Field("b", DataType.float64())])
    build = Schema([Field("k2", DataType.int64()),
                    Field("g", DataType.int64()),
                    Field("w", DataType.float64())])
    prog = F.get_fused_join_agg(
        group_exprs=[col("g")],
        child_exprs=[(col("b") * col("w")).alias("__v0__")],
        ops=("sum",), probe_pred=None, post_pred=None,
        lkey="k", rkey="k2", src_schema=src, build_schema=build,
        fused_ops=("HashJoin", "Project", "Aggregate"))
    if prog is None:
        raise RuntimeError("representative join_agg program did not lower")
    C = 64
    p_arrays = {"k": np.arange(C, dtype=np.int64),
                "b": np.ones(C, np.float64)}
    p_valids = {k: np.ones(C, bool) for k in p_arrays}
    b_arrays = {"g": np.arange(C, dtype=np.int64),
                "w": np.ones(C, np.float64)}
    b_valids = {k: np.ones(C, bool) for k in b_arrays}
    mask = np.ones(C, bool)
    b_sorted = np.arange(C, dtype=np.int64)
    b_perm = np.arange(C, dtype=np.int32)
    b_live = np.int32(C)
    return jax.make_jaxpr(
        lambda pa, pv, pm, ba, bv, bs, bp, bl: prog._run_packed(
            pa, pv, pm, (), ba, bv, bs, bp, bl, (), W=128, out_cap=32))(
        p_arrays, p_valids, mask, b_arrays, b_valids,
        b_sorted, b_perm, b_live)


def check_fusion_region_contracts() -> List[Finding]:
    """Round 21's fusion-region contract, re-proved from fresh jaxprs."""
    out: List[Finding] = []
    from . import dispatch_registry as reg
    for sid in REGION_SITES:
        if reg.budget_for(sid) is None:
            out.append(Finding(
                "fusion-region-contract", FRAGMENT_PATH, 1,
                f"region dispatch site {sid!r} is undeclared or exempt in "
                f"the dispatch registry — fusion regions must carry a "
                f"finite per-signature trace budget"))
    jaxprs = (("chain region", _region_chain_jaxpr(False)),
              ("topk region", _region_chain_jaxpr(True)),
              ("join_agg region", _region_join_agg_jaxpr()))
    for label, jx in jaxprs:
        for prim in FORBIDDEN_IN_FUSED_JOIN:
            k = count_primitive(jx.jaxpr, prim)
            if k:
                out.append(Finding(
                    "fusion-region-contract", FRAGMENT_PATH, 1,
                    f"{label} program contains {k} {prim} primitive(s) — "
                    f"whole-query compilation forbids host round-trips "
                    f"inside a fused region"))
        ops = max_sort_operands(jx.jaxpr)
        if ops > ARGSORT_MAX_SORT_OPERANDS:
            out.append(Finding(
                "fusion-region-contract", FRAGMENT_PATH, 1,
                f"{label} program sorts with {ops} operands (contract: "
                f"≤{ARGSORT_MAX_SORT_OPERANDS}) — the packed-code sort "
                f"budget applies inside regions too"))
    return out


def check_dispatch_contracts() -> List[Finding]:
    """Re-prove PR 1's dispatch contracts from freshly-built jaxprs."""
    out: List[Finding] = []
    try:
        for n_keys, dtype in ARGSORT_CASES:
            ops = max_sort_operands(argsort_jaxpr(n_keys, dtype).jaxpr)
            if ops > ARGSORT_MAX_SORT_OPERANDS:
                out.append(Finding(
                    "dispatch-contract", KERNELS_PATH, 1,
                    f"argsort_kernel({n_keys} {dtype} keys) compiles to a "
                    f"{ops}-operand lax.sort (contract: ≤"
                    f"{ARGSORT_MAX_SORT_OPERANDS}) — the operand-count "
                    f"compile cliff is back"))
        ops = max_sort_operands(grouped_agg_jaxpr().jaxpr)
        if ops > ARGSORT_MAX_SORT_OPERANDS:
            out.append(Finding(
                "dispatch-contract", KERNELS_PATH, 1,
                f"grouped_agg_block_impl sorts with {ops} operands "
                f"(contract: ≤{ARGSORT_MAX_SORT_OPERANDS})"))
        jx = join_fused_jaxpr()
        for prim in FORBIDDEN_IN_FUSED_JOIN:
            n = count_primitive(jx.jaxpr, prim)
            if n:
                out.append(Finding(
                    "dispatch-contract", KERNELS_PATH, 1,
                    f"join_fused_impl contains {n} {prim} primitive(s) — "
                    f"the single-dispatch contract forbids host "
                    f"round-trips inside the fused program"))
        if max_sort_operands(jx.jaxpr) > ARGSORT_MAX_SORT_OPERANDS:
            out.append(Finding(
                "dispatch-contract", KERNELS_PATH, 1,
                f"join_fused_impl build-side sort exceeds "
                f"{ARGSORT_MAX_SORT_OPERANDS} operands"))
        out.extend(check_fusion_region_contracts())
    except Exception as exc:   # can't verify ⇒ say so, don't pass silently
        out.append(Finding(
            "dispatch-contract", KERNELS_PATH, 1,
            f"could not re-verify dispatch contracts: {exc!r} (run with "
            f"--no-contracts to skip)"))
    return out
