"""Rule family 6 — donated-buffer safety for the device kernel plane.

The r12 megakernels donate input HBM to the fused program
(``donate_argnums`` / ``FusedAggProgram.donate_fn``): after a donating
dispatch the donated planes are DEAD — XLA has reused their memory for
the program's intermediates. Reading them afterwards returns garbage (or
crashes on silicon with a deleted-buffer error that CPU runs never see,
which is exactly why this must be a static check). Two rules:

- ``donated-buffer-read`` — taint the argument positions named by a
  ``donate_argnums`` jit wrapper (or a same-module helper that forwards
  its parameters into one — the ``_dispatch_packed`` pattern) at each
  dispatch site, propagate forward over the CFG, kill the taint on
  rebind (the overflow re-dispatch's ``dt = reencode()``), and flag any
  later read of a *plane-carrying* attribute (``.columns``,
  ``.row_mask``, ``.data``, ``.validity``) of a tainted name — in the
  dispatching function, or via a one-level same-module callee that reads
  planes off the corresponding parameter. Scalar metadata
  (``.row_count``, ``.capacity``, dictionaries) stays host-side and is
  deliberately NOT flagged.
- ``donation-unguarded`` — the static proof that
  ``DeviceTable.resident`` guards every donation of a potentially
  cache-shared table: a ``donate`` flag must derive from a direct
  ``.resident`` read, a call to a helper whose body reads ``.resident``
  (``_donation_ok``), or be a plain parameter passthrough (the caller
  already proved it). A bare ``donate=True`` or a guard that never
  consults residency donates buffers the HBM cache may still be serving.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from . import dataflow
from .dataflow import ModuleIndex
from .framework import Finding, SourceFile
from .rule_resources import _header_parts, walk_local

#: modules the donation discipline applies to (the device kernel plane)
DEVICE_MODULES = (
    "daft_tpu/device/fragment.py",
    "daft_tpu/device/kernels.py",
    "daft_tpu/device/runtime.py",
)

#: attributes that reach the donated device planes; everything else on a
#: DeviceTable (row_count, capacity, dictionaries) is host metadata
PLANE_ATTRS = frozenset({"columns", "row_mask", "data", "validity"})

RULE_IDS = {
    "donated-buffer-read": (
        "donation",
        "re-encode (dt = reencode()) or drop the donated object before "
        "touching its planes; donated HBM is dead after dispatch"),
    "donation-unguarded": (
        "donation",
        "derive the donate flag from DeviceTable.resident (e.g. via "
        "_donation_ok) so cache-shared buffers are never donated"),
}


def _call_last(call: ast.Call) -> str:
    return dataflow._call_last_name(call)


def _donating_jit_names(fn: ast.AST) -> Set[str]:
    """Local names bound (possibly conditionally) to
    ``jax.jit(..., donate_argnums=<non-empty-able>)`` wrappers."""
    out: Set[str] = set()
    for sub in walk_local(fn):
        if not (isinstance(sub, ast.Assign) and len(sub.targets) == 1
                and isinstance(sub.targets[0], ast.Name)):
            continue
        v = sub.value
        if isinstance(v, ast.Call) and _call_last(v) == "jit":
            for kw in v.keywords:
                if kw.arg == "donate_argnums" \
                        and not (isinstance(kw.value, ast.Tuple)
                                 and not kw.value.elts):
                    out.add(sub.targets[0].id)
    return out


def _donate_positions(fn: ast.AST, name: str) -> Optional[Tuple[int, ...]]:
    """The positions a donating wrapper donates, when statically evident
    (a tuple literal, possibly behind ``<tuple> if donate else ()``)."""
    for sub in walk_local(fn):
        if isinstance(sub, ast.Assign) and len(sub.targets) == 1 \
                and isinstance(sub.targets[0], ast.Name) \
                and sub.targets[0].id == name \
                and isinstance(sub.value, ast.Call):
            for kw in sub.value.keywords:
                if kw.arg != "donate_argnums":
                    continue
                v = kw.value
                if isinstance(v, ast.IfExp):
                    v = v.body
                if isinstance(v, ast.Tuple) and all(
                        isinstance(e, ast.Constant) for e in v.elts):
                    return tuple(int(e.value) for e in v.elts)
    return None


def _param_names(fn: ast.AST) -> List[str]:
    a = fn.args
    return [p.arg for p in a.posonlyargs + a.args]


def _forwarding_donors(idx: ModuleIndex) -> Dict[str, Set[int]]:
    """Same-module helpers that forward parameters into a donating
    dispatch (``_dispatch_packed``): helper name → the indices of ITS
    parameters whose values may be donated. One call level, which is the
    depth the codebase uses."""
    out: Dict[str, Set[int]] = {}
    for _, fn in idx.functions:
        donors = _donating_jit_names(fn)
        donate_fn_vars = {
            s.targets[0].id for s in walk_local(fn)
            if isinstance(s, ast.Assign) and len(s.targets) == 1
            and isinstance(s.targets[0], ast.Name)
            and isinstance(s.value, ast.IfExp)
            and isinstance(s.value.body, ast.Call)
            and _call_last(s.value.body) == "donate_fn"}
        if not donors and not donate_fn_vars:
            continue
        params = _param_names(fn)
        tainted_params: Set[int] = set()
        # which locals derive from which parameter (single assignment
        # depth — enough for the arrays/valids-from-dt pattern)
        derived: Dict[str, Set[str]] = {p: {p} for p in params}
        for s in walk_local(fn):
            if isinstance(s, ast.Assign) and len(s.targets) == 1 \
                    and isinstance(s.targets[0], ast.Name):
                roots = {n.id for n in ast.walk(s.value)
                         if isinstance(n, ast.Name)}
                derived[s.targets[0].id] = set().union(
                    *(derived.get(r, set()) for r in roots)) or set()
        for sub in walk_local(fn):
            if not isinstance(sub, ast.Call):
                continue
            callee = sub.func
            callee_name = callee.id if isinstance(callee, ast.Name) else ""
            if callee_name in donors:
                pos = _donate_positions(fn, callee_name) or tuple(
                    range(len(sub.args)))
                for i in pos:
                    if i < len(sub.args):
                        for n in ast.walk(sub.args[i]):
                            if isinstance(n, ast.Name):
                                for root in derived.get(n.id, set()):
                                    if root in params:
                                        tainted_params.add(
                                            params.index(root))
            elif callee_name in donate_fn_vars:
                for i in (0, 1):
                    if i < len(sub.args):
                        for n in ast.walk(sub.args[i]):
                            if isinstance(n, ast.Name):
                                for root in derived.get(n.id, set()):
                                    if root in params:
                                        tainted_params.add(
                                            params.index(root))
        if tainted_params:
            out[fn.name] = tainted_params
    return out


def _plane_readers(idx: ModuleIndex) -> Dict[str, Set[int]]:
    """helper name → parameter indices whose PLANE_ATTRS the helper
    reads (the one-level callee side of donated-then-read)."""
    out: Dict[str, Set[int]] = {}
    for _, fn in idx.functions:
        params = _param_names(fn)
        hit: Set[int] = set()
        for sub in walk_local(fn):
            if isinstance(sub, ast.Attribute) and sub.attr in PLANE_ATTRS \
                    and isinstance(sub.value, ast.Name) \
                    and sub.value.id in params:
                hit.add(params.index(sub.value.id))
        if hit:
            out[fn.name] = hit
    return out


def _donation_sites(fn: ast.AST, forwarding: Dict[str, Set[int]],
                    idx: ModuleIndex
                    ) -> List[Tuple[ast.Call, Set[str], Set[str]]]:
    """(call, tainted local names, donate-flag names) for every donating
    dispatch in fn. The flag names drive the correlated-kill rule: a
    rebind under ``if <flag>:`` kills the taint unconditionally, because
    the taint only exists when the flag was true."""
    donors = _donating_jit_names(fn)
    sites: List[Tuple[ast.Call, Set[str], Set[str]]] = []
    for sub in walk_local(fn):
        if not isinstance(sub, ast.Call):
            continue
        callee = sub.func
        name = callee.id if isinstance(callee, ast.Name) else ""
        tainted: Set[str] = set()
        flags: Set[str] = set()
        if name in donors:
            pos = _donate_positions(fn, name) or ()
            for i in pos:
                if i < len(sub.args) and isinstance(sub.args[i], ast.Name):
                    tainted.add(sub.args[i].id)
        elif name in forwarding:
            callee_def = idx.defs.get(name)
            callee_params = _param_names(callee_def) \
                if callee_def is not None else []
            flag = _donate_flag_value(sub, callee_def, callee_params)
            if isinstance(flag, ast.Constant) and not flag.value:
                continue  # statically donate=False
            if isinstance(flag, ast.Name):
                flags.add(flag.id)
            for i in forwarding[name]:
                if i < len(sub.args) and isinstance(sub.args[i], ast.Name):
                    tainted.add(sub.args[i].id)
            for kw in sub.keywords:
                if kw.arg in callee_params and isinstance(kw.value,
                                                          ast.Name):
                    # keyword passthrough into a tainted param position
                    if callee_params.index(kw.arg) in forwarding[name]:
                        tainted.add(kw.value.id)
        if tainted:
            sites.append((sub, tainted, flags))
    return sites


def _donate_flag_value(call: ast.Call, callee_def,
                       callee_params: List[str]) -> Optional[ast.AST]:
    """The expression the call passes for the callee's ``donate``
    parameter — positionally, by keyword, or the default (a missing
    donate=False default means the call does not donate)."""
    if "donate" not in callee_params:
        return None
    di = callee_params.index("donate")
    if di < len(call.args):
        return call.args[di]
    for kw in call.keywords:
        if kw.arg == "donate":
            return kw.value
    if callee_def is not None:
        a = callee_def.args
        defaults = a.defaults
        params = a.posonlyargs + a.args
        off = len(params) - len(defaults)
        if di >= off:
            return defaults[di - off]
    return None


def _check_donated_reads(sf: SourceFile, idx: ModuleIndex,
                         out: List[Finding]) -> None:
    forwarding = _forwarding_donors(idx)
    readers = _plane_readers(idx)
    for fname, fn in idx.functions:
        sites = _donation_sites(fn, forwarding, idx)
        if not sites:
            continue
        cfg = idx.cfg(fn)
        for call, tainted, flags in sites:
            stmt = _stmt_of(fn, cfg, call)
            if stmt is None:
                continue
            # taint flows from the dispatch's NORMAL successors only: an
            # exception raised BY the dispatch (a trace-time failure)
            # means no executable consumed the buffers, so that path
            # re-dispatches legitimately
            start_nodes = []
            for node in cfg.nodes_for(stmt):
                start_nodes.extend(t for t, is_exc in node.succ
                                   if not is_exc)
            # forward reach from the dispatch, killed at rebinds; a
            # rebind under `if <donate-flag>:` kills on BOTH branches —
            # the flag false means nothing was donated in the first
            # place (correlated-branch soundness)
            kills = _rebind_stmts(fn, tainted)
            for sub2 in walk_local(fn):
                if isinstance(sub2, ast.If) \
                        and isinstance(sub2.test, ast.Name) \
                        and sub2.test.id in flags \
                        and any(id(s) in kills
                                for s in ast.walk(sub2)
                                if isinstance(s, ast.stmt)):
                    kills.add(id(sub2))
            reads = _plane_read_stmts(fn, tainted, readers, idx)
            seen: Set[int] = set()
            stack = list(start_nodes)
            while stack:
                n = stack.pop()
                if id(n) in seen:
                    continue
                seen.add(id(n))
                if n.stmt is not None and id(n.stmt) in kills:
                    continue
                hit = reads.get(id(n.stmt)) if n.stmt is not None else None
                if hit is not None:
                    out.append(Finding(
                        "donated-buffer-read", sf.path, hit[1],
                        f"{hit[0]} is read at line {hit[1]} after the "
                        f"donating dispatch at line {call.lineno} in "
                        f"{fname}() — donated planes are dead; re-encode "
                        f"before reuse"))
                    reads.pop(id(n.stmt))
                for t, _ in n.succ:
                    stack.append(t)


def _rebind_stmts(fn: ast.AST, names: Set[str]) -> Set[int]:
    out: Set[int] = set()
    for sub in walk_local(fn):
        if isinstance(sub, ast.Assign):
            for t in sub.targets:
                if isinstance(t, ast.Name) and t.id in names:
                    out.add(id(sub))
    return out


def _plane_read_stmts(fn: ast.AST, names: Set[str],
                      readers: Dict[str, Set[int]], idx: ModuleIndex
                      ) -> Dict[int, Tuple[str, int]]:
    """id(stmt) → (description, line) for statements whose CFG-visible
    header reads donated planes of a tainted name (directly, or by
    passing it to a same-module plane-reading helper)."""
    out: Dict[int, Tuple[str, int]] = {}
    for stmt in walk_local(fn):
        if not isinstance(stmt, ast.stmt):
            continue
        for part in _header_parts(stmt):
            for sub in walk_local(part):
                if isinstance(sub, ast.Attribute) \
                        and sub.attr in PLANE_ATTRS \
                        and isinstance(sub.value, ast.Name) \
                        and sub.value.id in names:
                    out.setdefault(
                        id(stmt),
                        (f"{sub.value.id}.{sub.attr}", sub.lineno))
                if isinstance(sub, ast.Call):
                    cn = sub.func.id if isinstance(sub.func, ast.Name) \
                        else ""
                    if cn in readers:
                        for i in readers[cn]:
                            if i < len(sub.args) \
                                    and isinstance(sub.args[i], ast.Name) \
                                    and sub.args[i].id in names:
                                out.setdefault(
                                    id(stmt),
                                    (f"{sub.args[i].id} (via {cn}(), "
                                     f"which reads its planes)",
                                     sub.lineno))
    return out


def _stmt_of(fn, cfg, target):
    from .rule_resources import _stmt_of as impl
    return impl(fn, cfg, target)


# --------------------------------------------------- donation-unguarded

def _resident_summary(idx: ModuleIndex) -> Set[str]:
    """Functions whose body reads ``.resident`` (one level)."""
    out: Set[str] = set()
    for _, fn in idx.functions:
        for sub in walk_local(fn):
            if isinstance(sub, ast.Attribute) and sub.attr == "resident":
                out.add(fn.name)
                break
    return out


def _check_unguarded(sf: SourceFile, idx: ModuleIndex,
                     out: List[Finding]) -> None:
    resident_fns = _resident_summary(idx)
    for fname, fn in idx.functions:
        params = set(_param_names(fn))
        for sub in walk_local(fn):
            expr = None
            line = 0
            if isinstance(sub, ast.Assign) and len(sub.targets) == 1 \
                    and isinstance(sub.targets[0], ast.Name) \
                    and sub.targets[0].id == "donate":
                expr, line = sub.value, sub.lineno
            elif isinstance(sub, ast.Call):
                for kw in sub.keywords:
                    if kw.arg == "donate":
                        expr, line = kw.value, kw.value.lineno
            if expr is None:
                continue
            if isinstance(expr, ast.Constant) and expr.value is False:
                continue
            if isinstance(expr, ast.Name) and expr.id in params | {
                    "donate"}:
                continue  # passthrough: the producer site is checked
            ok = False
            for n in ast.walk(expr):
                if isinstance(n, ast.Attribute) and n.attr == "resident":
                    ok = True
                if isinstance(n, ast.Call):
                    cn = dataflow._call_last_name(n)
                    if cn in resident_fns:
                        ok = True
            if not ok:
                out.append(Finding(
                    "donation-unguarded", sf.path, line,
                    f"donate flag in {fname}() never consults "
                    f"DeviceTable.resident — a cache-shared table's "
                    f"buffers must not be donated (use _donation_ok)"))
    # bare `.donate_fn()` selections must live in a function that guards
    # (directly or via a resident-reading helper feeding the selector)
    for fname, fn in idx.functions:
        if fn.name == "donate_fn":
            continue
        for sub in walk_local(fn):
            if isinstance(sub, ast.Call) and isinstance(
                    sub.func, ast.Attribute) \
                    and sub.func.attr == "donate_fn":
                guarded = False
                for n in walk_local(fn):
                    if isinstance(n, ast.Attribute) \
                            and n.attr == "resident":
                        guarded = True
                    if isinstance(n, ast.Name) and n.id == "donate":
                        guarded = True  # flag-driven; the flag is checked
                if not guarded:
                    out.append(Finding(
                        "donation-unguarded", sf.path, sub.lineno,
                        f"donate_fn() selected in {fname}() without a "
                        f"donate flag or resident guard in scope"))


# ---------------------------------------------------------------- check

def check(sources: List[SourceFile]) -> List[Finding]:
    out: List[Finding] = []
    for sf in sources:
        if sf.path not in DEVICE_MODULES:
            continue
        idx = ModuleIndex(sf.tree)
        _check_donated_reads(sf, idx, out)
        _check_unguarded(sf, idx, out)
    return out
