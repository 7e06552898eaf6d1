"""Expression → fused XLA program compiler.

The device twin of the reference's expression evaluator
(``eval_expression_list``, ``src/daft-recordbatch/src/lib.rs:755``): a whole
projection/predicate list compiles into ONE jit function over the
DeviceTable's arrays, so XLA fuses the elementwise graph into a single kernel
(SURVEY.md §7.2: "compile a bound expression projection/filter into one fused
jit function per (schema, expr-set) with a compile cache keyed on padded
shapes").

String semantics ride on *sorted-dictionary codes*: comparisons against string
literals become integer comparisons against per-batch literal ranks, which are
computed host-side by "scalar specs" and passed as dynamic args (no recompile
per batch).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np
import pyarrow as pa

import jax
import jax.numpy as jnp

from ..datatype import DataType
from ..expressions.expressions import Expression
from ..schema import Schema

# ops the device compiler understands ------------------------------------
_ARITH = {"add", "sub", "mul", "div", "floordiv", "mod", "pow"}
_CMP = {"lt", "le", "gt", "ge", "eq", "neq"}
_BOOL = {"and", "or", "xor"}
_UNARY_F = {"sqrt": jnp.sqrt, "exp": jnp.exp, "ln": jnp.log, "log2": jnp.log2,
            "log10": jnp.log10, "sin": jnp.sin, "cos": jnp.cos, "tan": jnp.tan,
            "arcsin": jnp.arcsin, "arccos": jnp.arccos, "arctan": jnp.arctan,
            "sinh": jnp.sinh, "cosh": jnp.cosh, "tanh": jnp.tanh,
            "cbrt": jnp.cbrt, "degrees": jnp.degrees, "radians": jnp.radians}


class NotCompilable(Exception):
    pass


class ScalarSpec:
    """Host-side per-batch preparation: computes a scalar/array argument from
    a string column's sorted dictionary (e.g. the rank of a literal)."""

    def __init__(self, col: str, fn: Callable[[pa.Array], np.ndarray]):
        self.col = col
        self.fn = fn


def _dict_np(d: pa.Array) -> np.ndarray:
    return np.asarray(d.to_pylist(), dtype=object)


def _rank_spec(col: str, lit, side: str) -> ScalarSpec:
    def fn(d: pa.Array) -> np.ndarray:
        dn = _dict_np(d)
        if side == "eq":
            i = np.searchsorted(dn, lit)
            if i < len(dn) and dn[i] == lit:
                return np.int32(i)
            return np.int32(-1)
        i = np.searchsorted(dn, lit, side=side)
        return np.int32(i)
    return ScalarSpec(col, fn)


class Compiled:
    """A compiled projection: jitted fn + per-batch scalar preparation."""

    def __init__(self, fn, scalar_specs: List[ScalarSpec],
                 out_fields, needs_cols: List[str]):
        self.fn = fn
        self.scalar_specs = scalar_specs
        self.out_fields = out_fields
        self.needs_cols = needs_cols


class _Ctx:
    def __init__(self, schema: Schema):
        self.schema = schema
        self.scalar_specs: List[ScalarSpec] = []
        self.needs: List[str] = []

    def add_scalar(self, spec: ScalarSpec) -> int:
        self.scalar_specs.append(spec)
        return len(self.scalar_specs) - 1

    def need(self, col: str):
        if col not in self.needs:
            self.needs.append(col)


def _f64(backend_f32: bool):
    return jnp.float32 if backend_f32 else jnp.float64


def compile_projection(exprs: List[Expression], schema: Schema,
                       jit: bool = True) -> Compiled:
    """Compile an expression list; raises NotCompilable on unsupported ops.

    With ``jit=False`` the returned fn is the raw traceable composition, for
    embedding into larger fused programs (scan fragments)."""
    from .column import supports_f64
    ctx = _Ctx(schema)
    builders = [_build(e, ctx, not supports_f64()) for e in exprs]
    out_fields = [e.to_field(schema) for e in exprs]

    def run(arrays, valids, row_mask, scalars):
        env = (arrays, valids, row_mask, scalars)
        outs = []
        for b in builders:
            v, m = b(env)
            if v.ndim == 0:  # scalar literal broadcast
                v = jnp.broadcast_to(v, row_mask.shape)
                m = jnp.broadcast_to(m, row_mask.shape)
            outs.append((v, m))
        return tuple(outs)

    return Compiled(jax.jit(run) if jit else run, ctx.scalar_specs,
                    out_fields, ctx.needs)


def can_compile(e: Expression, schema: Schema) -> bool:
    from .column import supports_f64
    try:
        e.to_field(schema)
        _build(e, _Ctx(schema), not supports_f64())
        return True
    except (NotCompilable, NotImplementedError, ValueError, TypeError,
            KeyError, OverflowError):
        return False


def _dtype_of(e: Expression, ctx: _Ctx) -> DataType:
    return e.to_field(ctx.schema).dtype


def _is_str(e: Expression, ctx) -> bool:
    try:
        return _dtype_of(e, ctx).is_string()
    except Exception:
        return False


def _build(e: Expression, ctx: _Ctx, f32: bool):
    """Returns closure env -> (value_array, valid_array)."""
    op = e.op

    if op == "col":
        name = e.params[0]
        if name not in ctx.schema:
            raise NotCompilable(f"unknown column {name}")
        dt = ctx.schema[name].dtype
        if dt.device_repr() is None:
            raise NotCompilable(f"column {name}: {dt!r} not device-representable")
        ctx.need(name)
        return lambda env: (env[0][name], env[1][name])

    if op == "alias":
        return _build(e.args[0], ctx, f32)

    if op == "lit":
        v = e.params[0]
        if v is None:
            return lambda env: (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.bool_))
        if isinstance(v, bool):
            c = jnp.asarray(v)
        elif isinstance(v, int):
            if f32 and not (-(2**31) <= v < 2**31):
                raise NotCompilable("int literal exceeds int32 on f32 backend")
            if not (-(2**63) <= v < 2**63):
                raise NotCompilable("int literal exceeds int64")
            c = jnp.asarray(v, jnp.int64 if not f32 else jnp.int32)
        elif isinstance(v, float):
            c = jnp.asarray(v, jnp.float32 if f32 else jnp.float64)
        else:
            import datetime
            if isinstance(v, datetime.datetime):
                c = jnp.asarray(int(v.timestamp() * 1_000_000), jnp.int64)
            elif isinstance(v, datetime.date):
                c = jnp.asarray((v - datetime.date(1970, 1, 1)).days, jnp.int32)
            else:
                raise NotCompilable(f"literal {type(v)} not device-representable")
        return lambda env: (c, jnp.ones((), jnp.bool_))

    if op == "cast":
        target = e.params[0]
        child_dt = _dtype_of(e.args[0], ctx)
        if child_dt.is_string() and not target.is_string():
            raise NotCompilable("string cast on device")
        rep = target.device_repr()
        if rep is None or target.is_string():
            raise NotCompilable(f"cast to {target!r} on device")
        c = _build(e.args[0], ctx, f32)
        jdt = jnp.dtype(rep) if not (rep == np.float64 and f32) else jnp.float32
        return lambda env: (lambda v_m: (v_m[0].astype(jdt), v_m[1]))(c(env))

    # string comparisons against literals --------------------------------
    if op in _CMP:
        l, r = e.args
        l_str, r_str = _is_str(l, ctx), _is_str(r, ctx)
        if l_str or r_str:
            return _build_str_cmp(e, ctx, f32)

    if op in _ARITH or op in _CMP:
        cl = _build(e.args[0], ctx, f32)
        cr = _build(e.args[1], ctx, f32)
        ldt, rdt = _dtype_of(e.args[0], ctx), _dtype_of(e.args[1], ctx)
        if ldt.is_temporal() or rdt.is_temporal():
            if op in _ARITH and not (op in ("sub", "add")):
                raise NotCompilable("temporal arithmetic beyond add/sub")

        def fn(env, _op=op):
            lv, lm = cl(env)
            rv, rm = cr(env)
            m = lm & rm
            if _op == "add":
                v = lv + rv
            elif _op == "sub":
                v = lv - rv
            elif _op == "mul":
                v = lv * rv
            elif _op == "div":
                # IEEE semantics (matches the host tier): x/0 = ±inf, 0/0 = nan
                dt = jnp.float32 if f32 else jnp.float64
                v = lv.astype(dt) / rv.astype(dt)
            elif _op == "floordiv":
                v = jnp.floor_divide(lv, jnp.where(rv == 0, 1, rv))
            elif _op == "mod":
                v = jnp.mod(lv, jnp.where(rv == 0, 1, rv))
            elif _op == "pow":
                v = jnp.power(lv.astype(jnp.float32 if f32 else jnp.float64), rv)
            elif _op == "lt":
                v = lv < rv
            elif _op == "le":
                v = lv <= rv
            elif _op == "gt":
                v = lv > rv
            elif _op == "ge":
                v = lv >= rv
            elif _op == "eq":
                v = lv == rv
            else:
                v = lv != rv
            return v, m
        return fn

    if op in _BOOL:
        cl = _build(e.args[0], ctx, f32)
        cr = _build(e.args[1], ctx, f32)
        ldt = _dtype_of(e.args[0], ctx)
        if ldt.is_integer():
            jop = {"and": jnp.bitwise_and, "or": jnp.bitwise_or,
                   "xor": jnp.bitwise_xor}[op]
            return lambda env: (lambda a, b: (jop(a[0], b[0]), a[1] & b[1]))(
                cl(env), cr(env))

        def bfn(env, _op=op):
            lv, lm = cl(env)
            rv, rm = cr(env)
            lv = lv.astype(jnp.bool_)
            rv = rv.astype(jnp.bool_)
            if _op == "and":
                # Kleene: F & x = F even if x null
                v = lv & rv
                m = (lm & rm) | (lm & ~lv) | (rm & ~rv)
            elif _op == "or":
                v = lv | rv
                m = (lm & rm) | (lm & lv) | (rm & rv)
            else:
                v = lv ^ rv
                m = lm & rm
            return v, m
        return bfn

    if op == "not":
        c = _build(e.args[0], ctx, f32)
        return lambda env: (lambda v_m: (~v_m[0].astype(jnp.bool_), v_m[1]))(c(env))
    if op == "negate":
        c = _build(e.args[0], ctx, f32)
        return lambda env: (lambda v_m: (-v_m[0], v_m[1]))(c(env))
    if op == "abs":
        c = _build(e.args[0], ctx, f32)
        return lambda env: (lambda v_m: (jnp.abs(v_m[0]), v_m[1]))(c(env))
    if op == "is_null":
        c = _build(e.args[0], ctx, f32)
        return lambda env: (lambda v_m: (~v_m[1], jnp.ones_like(v_m[1])))(c(env))
    if op == "not_null":
        c = _build(e.args[0], ctx, f32)
        return lambda env: (lambda v_m: (v_m[1], jnp.ones_like(v_m[1])))(c(env))
    if op == "fill_null":
        if _is_str(e.args[0], ctx):
            raise NotCompilable("fill_null on strings")
        c = _build(e.args[0], ctx, f32)
        cf = _build(e.args[1], ctx, f32)

        def ffn(env):
            v, m = c(env)
            fv, fm = cf(env)
            return jnp.where(m, v, fv.astype(v.dtype)), m | fm
        return ffn
    if op == "between":
        inner = Expression("and", (Expression("ge", (e.args[0], e.args[1])),
                                   Expression("le", (e.args[0], e.args[2]))))
        return _build(inner, ctx, f32)
    if op == "is_in":
        target = e.args[0]
        items = e.args[1:]
        if not all(i.op == "lit" for i in items):
            raise NotCompilable("is_in with non-literal items")
        if _is_str(target, ctx):
            src = target._unalias()
            if src.op != "col":
                raise NotCompilable("string is_in on computed values")
            ctx.need(src.params[0])
            lits = [i.params[0] for i in items]

            def spec_fn(d: pa.Array) -> np.ndarray:
                dn = _dict_np(d)
                out = []
                for L in lits:
                    i = np.searchsorted(dn, L)
                    out.append(i if i < len(dn) and dn[i] == L else -1)
                return np.asarray(out, dtype=np.int32)
            si = ctx.add_scalar(ScalarSpec(src.params[0], spec_fn))
            name = src.params[0]
            # items x rows, the rows along the lanes: rows x items pads a
            # two-item list to a vector register's 128 lanes a row
            return lambda env: (
                (env[0][name][None, :] == env[3][si][:, None]).any(axis=0),
                env[1][name])
        c = _build(target, ctx, f32)
        vals = [i.params[0] for i in items]
        consts = jnp.asarray(np.asarray(vals))

        def ifn(env):
            v, m = c(env)
            return (v[None, :] == consts[:, None]).any(axis=0), m
        return ifn
    if op == "if_else":
        cp = _build(e.args[0], ctx, f32)
        ct = _build(e.args[1], ctx, f32)
        cf2 = _build(e.args[2], ctx, f32)
        if _is_str(e.args[1], ctx) or _is_str(e.args[2], ctx):
            raise NotCompilable("if_else over strings")

        def iefn(env):
            pv, pm = cp(env)
            tv, tm = ct(env)
            fv, fm = cf2(env)
            tv, fv = jnp.broadcast_arrays(tv, fv)
            v = jnp.where(pv.astype(jnp.bool_), tv, fv)
            m = jnp.where(pv.astype(jnp.bool_), tm, fm) & pm
            return v, m
        return iefn
    if op in ("ceil", "floor", "round", "sign"):
        c = _build(e.args[0], ctx, f32)
        j = {"ceil": jnp.ceil, "floor": jnp.floor, "sign": jnp.sign}.get(op)
        if op == "round":
            nd = e.params[0]
            return lambda env: (lambda v_m: (jnp.round(v_m[0], nd), v_m[1]))(c(env))
        return lambda env: (lambda v_m: (j(v_m[0]), v_m[1]))(c(env))
    if op in _UNARY_F:
        c = _build(e.args[0], ctx, f32)
        j = _UNARY_F[op]
        fdt = jnp.float32 if f32 else jnp.float64
        return lambda env: (lambda v_m: (j(v_m[0].astype(fdt)), v_m[1]))(c(env))
    if op == "log":
        c = _build(e.args[0], ctx, f32)
        base = math.log(e.params[0])
        fdt = jnp.float32 if f32 else jnp.float64
        return lambda env: (lambda v_m: (jnp.log(v_m[0].astype(fdt)) / base,
                                         v_m[1]))(c(env))
    if op == "clip":
        c = _build(e.args[0], ctx, f32)
        lo = e.args[1].params[0] if len(e.args) > 1 and e.args[1].op == "lit" else None
        hi = e.args[2].params[0] if len(e.args) > 2 and e.args[2].op == "lit" else None
        return lambda env: (lambda v_m: (
            jnp.clip(v_m[0], lo if lo is not None else -jnp.inf,
                     hi if hi is not None else jnp.inf), v_m[1]))(c(env))
    if op == "float.is_nan":
        c = _build(e.args[0], ctx, f32)
        return lambda env: (lambda v_m: (jnp.isnan(v_m[0]), v_m[1]))(c(env))
    if op == "float.is_inf":
        c = _build(e.args[0], ctx, f32)
        return lambda env: (lambda v_m: (jnp.isinf(v_m[0]), v_m[1]))(c(env))
    if op == "float.not_nan":
        c = _build(e.args[0], ctx, f32)
        return lambda env: (lambda v_m: (~jnp.isnan(v_m[0]), v_m[1]))(c(env))
    if op == "float.fill_nan":
        c = _build(e.args[0], ctx, f32)
        cf = _build(e.args[1], ctx, f32)

        def fnan(env):
            v, m = c(env)
            fv, _ = cf(env)
            return jnp.where(jnp.isnan(v), fv.astype(v.dtype), v), m
        return fnan

    if op in ("dt.year", "dt.month", "dt.day", "dt.day_of_week", "dt.quarter",
              "dt.hour", "dt.minute", "dt.second", "dt.date"):
        return _build_dt(e, ctx, f32)

    if op == "hash":
        c = _build(e.args[0], ctx, f32)

        def hfn(env):
            v, m = c(env)
            x = v.view(jnp.uint64) if v.dtype.itemsize == 8 else \
                v.astype(jnp.uint64)
            x = (x + jnp.uint64(0x9E3779B97F4A7C15))
            x = (x ^ (x >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
            x = (x ^ (x >> 27)) * jnp.uint64(0x94D049BB133111EB)
            return x ^ (x >> 31), jnp.ones_like(m)
        return hfn

    raise NotCompilable(f"device compile for {op}")


def _build_str_cmp(e: Expression, ctx: _Ctx, f32: bool):
    op = e.op
    l, r = e.args
    # normalize to (col, lit)
    flip = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le",
            "eq": "eq", "neq": "neq"}
    if l.op == "lit" and r.op != "lit":
        l, r = r, l
        op = flip[op]
    src = l._unalias()
    if src.op != "col" or r.op != "lit" or not isinstance(r.params[0], str):
        raise NotCompilable("string comparison requires col vs str literal")
    name = src.params[0]
    if name not in ctx.schema or not ctx.schema[name].dtype.is_string():
        raise NotCompilable("string cmp on non-string column")
    ctx.need(name)
    lit = r.params[0]
    if op == "eq":
        si = ctx.add_scalar(_rank_spec(name, lit, "eq"))
        return lambda env: (env[0][name] == env[3][si], env[1][name])
    if op == "neq":
        si = ctx.add_scalar(_rank_spec(name, lit, "eq"))
        return lambda env: (env[0][name] != env[3][si], env[1][name])
    if op == "lt":
        si = ctx.add_scalar(_rank_spec(name, lit, "left"))
        return lambda env: (env[0][name] < env[3][si], env[1][name])
    if op == "ge":
        si = ctx.add_scalar(_rank_spec(name, lit, "left"))
        return lambda env: (env[0][name] >= env[3][si], env[1][name])
    if op == "le":
        si = ctx.add_scalar(_rank_spec(name, lit, "right"))
        return lambda env: (env[0][name] < env[3][si], env[1][name])
    if op == "gt":
        si = ctx.add_scalar(_rank_spec(name, lit, "right"))
        return lambda env: (env[0][name] >= env[3][si], env[1][name])
    raise NotCompilable(op)


def _build_dt(e: Expression, ctx: _Ctx, f32: bool):
    """Civil-calendar decomposition on device (days-from-epoch integer math)."""
    fn = e.op[3:]
    child = e.args[0]
    cdt = _dtype_of(child, ctx)
    c = _build(child, ctx, f32)

    if cdt.kind == "timestamp":
        unit = cdt.timeunit.value
        per_day = {"s": 86_400, "ms": 86_400_000, "us": 86_400_000_000,
                   "ns": 86_400_000_000_000}[unit]
        per_sec = {"s": 1, "ms": 1_000, "us": 1_000_000, "ns": 1_000_000_000}[unit]
    elif cdt.kind == "date":
        per_day, per_sec = 1, None
    else:
        raise NotCompilable(f"dt.{fn} on {cdt!r}")

    def days_of(v):
        return jnp.floor_divide(v.astype(jnp.int64), per_day) if per_day != 1 \
            else v.astype(jnp.int64)

    def civil(z):
        z = z + 719468
        era = jnp.floor_divide(z, 146097)
        doe = z - era * 146097
        yoe = jnp.floor_divide(
            doe - jnp.floor_divide(doe, 1460) + jnp.floor_divide(doe, 36524)
            - jnp.floor_divide(doe, 146096), 365)
        y = yoe + era * 400
        doy = doe - (365 * yoe + jnp.floor_divide(yoe, 4)
                     - jnp.floor_divide(yoe, 100))
        mp = jnp.floor_divide(5 * doy + 2, 153)
        d = doy - jnp.floor_divide(153 * mp + 2, 5) + 1
        m = jnp.where(mp < 10, mp + 3, mp - 9)
        y = jnp.where(m <= 2, y + 1, y)
        return y, m, d

    def out(env):
        v, mvalid = c(env)
        days = days_of(v)
        if fn == "date":
            return days.astype(jnp.int32), mvalid
        if fn in ("year", "month", "day", "quarter"):
            y, m, d = civil(days)
            if fn == "year":
                return y.astype(jnp.int32), mvalid
            if fn == "month":
                return m.astype(jnp.uint32), mvalid
            if fn == "quarter":
                return (jnp.floor_divide(m - 1, 3) + 1).astype(jnp.uint32), mvalid
            return d.astype(jnp.uint32), mvalid
        if fn == "day_of_week":
            return ((days + 3) % 7).astype(jnp.uint32), mvalid  # 1970-01-01 = Thu
        secs = jnp.floor_divide(v.astype(jnp.int64), per_sec) if per_sec else None
        sod = secs - days * 86400
        if fn == "hour":
            return jnp.floor_divide(sod, 3600).astype(jnp.uint32), mvalid
        if fn == "minute":
            return (jnp.floor_divide(sod, 60) % 60).astype(jnp.uint32), mvalid
        if fn == "second":
            return (sod % 60).astype(jnp.uint32), mvalid
        raise NotCompilable(fn)
    return out
