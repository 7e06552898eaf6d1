"""The comparison that decides ``correct``: an engine's answer against the
plain reference's.

Column names and their order, row counts, keys, integers, strings and dates
are exact; a float is held to the configuration's ``rtol``, relative to the
reference's value. A top-k answer is compared by key: the reference hands
over its ranking beyond the cut, and a row whose ranking value lies within
``rtol`` of the value at the cut may be in or out. Returns the numbers
compared, so that every run can print them beside their limit.
"""

from __future__ import annotations

import math
from typing import Dict, List

Answer = Dict[str, list]


class Mismatch(Exception):
    """The answer differs from the reference beyond what is tolerated."""


def _rel(label: str, got, ref, rtol: float) -> float:
    if got is None or ref is None:
        if got is not ref:
            raise Mismatch(f"{label}: {got!r} != reference {ref!r}")
        return 0.0
    if not math.isfinite(got):
        raise Mismatch(f"{label}: non-finite {got!r}")
    err = abs(got - ref) / max(abs(ref), 1e-300)
    if err > rtol:
        raise Mismatch(f"{label}: {got!r} vs reference {ref!r} "
                       f"(relative error {err:.3e} > rtol {rtol:g})")
    return err


def _cell(label: str, got, ref, rtol: float) -> float:
    if isinstance(ref, float) or isinstance(got, float):
        return _rel(label, got, ref, rtol)
    if got != ref:
        raise Mismatch(f"{label}: {got!r} != reference {ref!r}")
    return 0.0


def _columns(label: str, got: Answer, ref: Answer) -> None:
    if list(got) != list(ref):
        raise Mismatch(f"{label}: columns {list(got)} != reference "
                       f"{list(ref)}")
    lengths = {len(v) for v in got.values()}
    if len(lengths) > 1:
        raise Mismatch(f"{label}: ragged answer {lengths}")


def _rows(label: str, got: Answer, ref: Answer, rtol: float) -> float:
    worst = 0.0
    for name in ref:
        if len(got[name]) != len(ref[name]):
            raise Mismatch(f"{label}.{name}: {len(got[name])} rows != "
                           f"reference {len(ref[name])}")
        for i, (a, b) in enumerate(zip(got[name], ref[name])):
            worst = max(worst, _cell(f"{label}.{name}[{i}]", a, b, rtol))
    return worst


def _topk(label: str, got: Answer, ref: Answer, spec: dict,
          rtol: float) -> float:
    k, keys, by = spec["k"], spec["keys"], spec["by"]
    n_ref = len(ref[by])
    n_got = len(got[by])
    if n_got != min(k, n_ref):
        raise Mismatch(f"{label}: {n_got} rows, expected {min(k, n_ref)}")
    if not n_got:
        return 0.0
    index = {tuple(ref[c][i] for c in keys): i for i in range(n_ref)}
    at_cut = ref[by][n_got - 1]
    worst = 0.0
    seen: List[int] = []
    for i in range(n_got):
        key = tuple(got[c][i] for c in keys)
        j = index.get(key)
        if j is None:
            raise Mismatch(f"{label}[{i}]: {dict(zip(keys, key))} is not "
                           f"among the reference's first {n_ref}")
        for name in ref:
            worst = max(worst, _cell(f"{label}.{name}[{i}]", got[name][i],
                                     ref[name][j], rtol))
        if ref[by][j] < at_cut * (1.0 - rtol):
            raise Mismatch(f"{label}[{i}]: {dict(zip(keys, key))} ranks "
                           f"{j} in the reference, beyond the cut at {k}")
        seen.append(j)
    for j in range(n_ref):
        if ref[by][j] > at_cut * (1.0 + rtol) and j not in seen:
            raise Mismatch(f"{label}: the reference's row {j} "
                           f"({by}={ref[by][j]!r}) is above the cut and "
                           f"missing")
    for a, b in zip(seen, seen[1:]):
        if ref[by][b] > ref[by][a] * (1.0 + rtol):
            raise Mismatch(f"{label}: rows out of order by {by} "
                           f"(reference ranks {a} before {b})")
    return worst


def compare(label: str, got: Answer, ref: Answer, spec: dict,
            rtol: float) -> float:
    """The worst relative error of a float in ``got``; raises
    :class:`Mismatch` on anything beyond the tolerance."""
    _columns(label, got, ref)
    if spec["kind"] == "rows":
        return _rows(label, got, ref, rtol)
    if spec["kind"] == "topk":
        return _topk(label, got, ref, spec, rtol)
    raise ValueError(f"unknown comparison {spec['kind']!r}")


def cut_to_answer(ref: Answer, spec: dict) -> Answer:
    """The answer a correct engine gives: the reference's ranking cut at
    ``k`` (for the control, which stands in the engine's place)."""
    if spec["kind"] != "topk":
        return ref
    return {c: v[:spec["k"]] for c, v in ref.items()}
