"""The query path's device programs COMPILE for the TPU — asked of the chip's
own compiler, for a described (not attached) ``v5e:2x2``, at no chip time
(``/opt/skills/guides/on-chip-measurement`` section 2).

CPU-backend tests cannot see what the TPU compiler refuses (PR 23 found a
set of Pallas kernels refused outright; they are gone), so the programs
TPC-H Q1/Q6/Q3 dispatch are lowered and compiled here: Q1's fused fragment
at the dense strategy every benchmark cell runs, the sort grouped-agg in
Q1's key/agg layout (what a non-dictionary key falls to), the fused join,
the packed-key argsort, Q6's fused scan->filter->agg fragment, the scan's
selection in Q19's and Q14's shapes at the star cell's real buckets (no sort
in it: 5 s each; the survivors come back by row gathers there),
and the mesh grouped-agg collective on a 4-device ``Mesh`` of the described
devices.
Capacities are moderate on purpose (16384-row sorts, ~25 s each):
``lax.sort`` compile time on this compiler grows with the bucket (ROADMAP
A8), and the whole file must stay under ~3 minutes in one worker.

Rules this file keeps (the guide's, because pytest-xdist imports every test
file in every worker and only ONE process may load libtpu): the topology is
described inside a module-scoped fixture — never at import, not autouse, not
in conftest.py; everything built from it (shardings, meshes, shapes) is
built in fixtures/tests; compiles run in this process; the persistent
compilation cache is off around them (a described-device compile is written
to it but can never be read back).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from daft_tpu.device import kernels


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _q1_sort_grouped_agg(S):
    """kernels.grouped_agg_impl in TPC-H Q1's layout: two int32 dictionary
    -code keys (l_returnflag, l_linestatus), seven f32 sums (f64 rides f32
    on the TPU) — the sort strategy, which a group-by takes when
    ``fragment.dense_plan`` declines (a key without a dictionary, or more
    than 4 096 slots); Q1 itself runs ``_fused_q1_dense``."""
    C = 16384
    ops = ("sum",) * 7
    keys = (S((C,), jnp.int32),) * 2
    vals = (S((C,), jnp.float32),) * 7
    bools = lambda n: (S((C,), jnp.bool_),) * n  # noqa: E731
    fn = jax.jit(kernels.grouped_agg_impl, static_argnames=("ops",))
    return fn.lower(keys, bools(2), vals, bools(7), S((C,), jnp.bool_),
                    ops=ops)


def _join_fused(S):
    """kernels.join_fused_impl on int64 group ids (joins.match_indices):
    4096-row probe side, 16384-row build side (the sorted one), FK-shaped
    output bucket — chip_smoke's Q3 customer-orders join at 64 parts."""
    cl, cr = 4096, 16384
    fn = jax.jit(kernels.join_fused_impl, static_argnames=("out_capacity",))
    return fn.lower(S((cl,), jnp.int64), S((cl,), jnp.bool_),
                    S((cl,), jnp.bool_), S((cr,), jnp.int64),
                    S((cr,), jnp.bool_), S((cr,), jnp.bool_),
                    out_capacity=cr)


def _packed_argsort(S):
    """kernels.argsort_kernel: Q3's (revenue desc f32, o_orderdate asc
    int32) top-k key layout through the packed-u64 radix words."""
    C = 16384
    return kernels.argsort_kernel.lower(
        (S((C,), jnp.float32), S((C,), jnp.int32)),
        (S((C,), jnp.bool_),) * 2, S((C,), jnp.bool_),
        descending=(True, False), nulls_first=(False, False))


def _lower_packed(S, C, prog, how):
    """``prog.packed_fn`` lowered over ``C``-row planes at ``how`` =
    ``(out_cap, strategy, dims)``."""
    out_cap, strategy, dims = how
    return prog.packed_fn.lower(*_packed_inputs(S, C, prog), out_cap=out_cap,
                                strategy=strategy, dims=dims)


def _packed_inputs(S, rows, prog):
    arrays = {n: S((rows,), prog.in_np_dtypes[n])
              for n in prog.compiled.needs_cols}
    valids = {n: S((rows,), jnp.bool_) for n in prog.compiled.needs_cols}
    assert not prog.compiled.scalar_specs
    return arrays, valids, S((rows,), jnp.bool_), ()


def _q6_program():
    """One fused scan->filter->project->agg fragment (fragment.get_fused_agg):
    TPC-H Q6's shape — predicate over date/float columns, a product, one
    global sum — as the single jit program the executor dispatches."""
    import datetime

    from daft_tpu import DataType, col, lit
    from daft_tpu.device import fragment
    from daft_tpu.schema import Field, Schema
    schema = Schema([Field("l_shipdate", DataType.date()),
                     Field("l_discount", DataType.float32()),
                     Field("l_quantity", DataType.float32()),
                     Field("l_extendedprice", DataType.float32())])
    pred = ((col("l_shipdate") >= lit(datetime.date(1994, 1, 1)))
            & (col("l_shipdate") < lit(datetime.date(1995, 1, 1)))
            & (col("l_quantity") < 24))
    child = [(col("l_extendedprice") * col("l_discount")).alias("__v0__")]
    prog = fragment.get_fused_agg([], child, ("sum",), pred, schema)
    assert prog is not None, "Q6-shaped fragment must be device-compilable"
    return prog, (fragment._OUT_CAP0, "sort", ())


def _fused_scan_filter_agg(S):
    C = 524288   # chip_smoke's lineitem bucket (SF1 in 16 parts)
    return _lower_packed(S, C, *_q6_program())


def _q1_program():
    """TPC-H Q1's fused scan->filter->project->agg fragment at
    ``strategy="dense"``: the program all five benchmark cells dispatch,
    once a lineitem file (``agg_hbm_pct`` is its device time). Two string
    keys as dictionary codes with their pow2 ``dims`` (3 return flags -> 4,
    2 line statuses -> 2: 15 slots in the 128 bucket), the date predicate,
    and the partial aggregates the planner hands the fragment: the query's
    four sums, and a (sum, count) pair for each of its three means, then
    its count."""
    import datetime

    from daft_tpu import DataType, col, lit
    from daft_tpu.device import fragment
    from daft_tpu.schema import Field, Schema
    f32 = DataType.float32()
    schema = Schema([Field("l_returnflag", DataType.string()),
                     Field("l_linestatus", DataType.string()),
                     Field("l_quantity", f32),
                     Field("l_extendedprice", f32),
                     Field("l_discount", f32), Field("l_tax", f32),
                     Field("l_shipdate", DataType.date())])
    disc_price = col("l_extendedprice") * (1 - col("l_discount"))
    charge = disc_price * (1 + col("l_tax"))
    qty, price, disc = (col("l_quantity"), col("l_extendedprice"),
                        col("l_discount"))
    children = [qty, price, disc_price, charge,
                qty, qty, price, price, disc, disc, qty]
    ops = ("sum", "sum", "sum", "sum",
           "sum", "count", "sum", "count", "sum", "count", "count")
    prog = fragment.get_fused_agg(
        [col(k).alias(k) for k in ("l_returnflag", "l_linestatus")],
        [c.alias(f"__v{i}__") for i, c in enumerate(children)], ops,
        col("l_shipdate") <= lit(datetime.date(1998, 9, 2)), schema)
    assert prog is not None, "Q1-shaped fragment must be device-compilable"
    assert prog.key_sources == ("l_returnflag", "l_linestatus")
    return prog, (fragment._OUT_CAP0, "dense", (4, 2))


def _fused_q1_dense(S):
    C = 524288   # the bucket of _fused_scan_filter_agg: SF1 in 16 parts
    return _lower_packed(S, C, *_q1_program())


#: the resident cells' bucket: an SF10 / SF100 lineitem file's 3.75 M rows
RESIDENT_C = 4194304


def _fused_q1_dense_resident(S):
    return _lower_packed(S, RESIDENT_C, *_q1_program())


def _wide_concatenates(text, C):
    """The ``concatenate`` instructions of ``text`` (StableHLO or HLO)
    that hold a ``C``-wide operand or result."""
    import re
    return [ln.strip() for ln in text.splitlines()
            if re.search(r"\bconcatenate\b", ln)
            and re.search(rf"[\[<x,]{C}[\]x>,]", ln)]


def _scan_select(S, columns, pred, strings, words, C, w):
    """The scan's selection (fragment.get_fused_region's chain program, as
    ``executor._scan_select`` runs it) over ``columns`` at the ``w`` rung
    of the ``C`` bucket; string tests ride runtime scalars of the table's
    dictionary (``strings``: one dictionary for every scalar)."""
    import pyarrow as pa

    from daft_tpu import col
    from daft_tpu.device import fragment
    from daft_tpu.schema import Field, Schema
    schema = Schema([Field(n, dt) for n, dt in columns])
    prog = fragment.get_fused_region(
        [col(c) for c in schema.column_names], pred, schema)
    assert prog is not None, "the selection must be device-compilable"
    assert prog.out_words == words
    # the survivors come back by gathers of 128-lane rows at these shapes
    assert fragment.gathers_rows(C, w)
    arrays = {n: S((C,), prog.in_np_dtypes[n])
              for n in prog.compiled.needs_cols}
    valids = {n: S((C,), jnp.bool_) for n in prog.compiled.needs_cols}
    scalars = tuple(
        S(np.shape(v), np.asarray(v).dtype) for v in (
            spec.fn(pa.array(strings))
            for spec in prog.compiled.scalar_specs))
    return prog.packed_fn.lower(arrays, valids, S((C,), jnp.bool_),
                                scalars, out_w=w)


def _scan_select_q19(S):
    """TPC-H Q19's shape at the benchmark cell's real sizes: two string
    predicates against runtime scalars of the table's dictionary, six
    columns out (an int64 key, three floats, two dictionary codes), the
    count-and-search compaction, the output stage's row gathers (eight
    int32 planes: the validity bits, the key's halves, five values) and
    the packed block at the 262 144 rung of the 4 194 304 bucket."""
    from daft_tpu import DataType, col
    f32 = DataType.float32()   # what a float64 rides on the chip
    lowered = _scan_select(
        S, [("l_partkey", DataType.int64()), ("l_quantity", f32),
            ("l_extendedprice", f32), ("l_discount", f32),
            ("l_shipinstruct", DataType.string()),
            ("l_shipmode", DataType.string())],
        ((col("l_shipinstruct") == "DELIVER IN PERSON")
         & col("l_shipmode").is_in(["AIR", "AIR REG"])
         & col("l_partkey").not_null()),
        ["AIR", "DELIVER IN PERSON"],
        5,   # header+validity, the key, 5 halves
        4194304, 262144)
    assert len(lowered.args_info[0][3]) == 2    # the two scalars
    return lowered


def _scan_select_q14(S):
    """TPC-H Q14's shape: a date range, four columns out (an int64 key,
    two floats, the date) at the 65 536 rung of the 4 194 304 bucket."""
    import datetime

    from daft_tpu import DataType, col, lit
    f32 = DataType.float32()
    return _scan_select(
        S, [("l_partkey", DataType.int64()), ("l_extendedprice", f32),
            ("l_discount", f32), ("l_shipdate", DataType.date())],
        ((col("l_shipdate") >= lit(datetime.date(1995, 9, 1)))
         & (col("l_shipdate") < lit(datetime.date(1995, 10, 1)))),
        [], 4, 4194304, 65536)


#: gathers of 128-lane rows / of single elements from a plane of the whole
#: table, in the compiled selection: the search's two row gathers and one
#: a plane of the output stage (the validity bits, a 64-bit key's halves,
#: a value each), and no element gather but the search's 256-entry lookup
SELECT_GATHERS = {"scan_select_q19": (2 + 8, 1),
                  "scan_select_q14": (2 + 6, 1)}


ONE_CHIP_PROGRAMS = {
    "scan_select_q19": _scan_select_q19,
    "scan_select_q14": _scan_select_q14,
    "fused_scan_filter_agg_q1_dense": _fused_q1_dense,
    "fused_scan_filter_agg_q1_dense_resident": _fused_q1_dense_resident,
    "sort_grouped_agg_q1_layout": _q1_sort_grouped_agg,
    "join_fused": _join_fused,
    "packed_key_argsort": _packed_argsort,
    "fused_scan_filter_agg_q6": _fused_scan_filter_agg,
}


@pytest.mark.parametrize("name", sorted(ONE_CHIP_PROGRAMS))
def test_program_compiles_for_one_v5e_chip(name, one_chip):
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = ONE_CHIP_PROGRAMS[name](S).compile()
    assert compiled.memory_analysis() is not None
    if name in SELECT_GATHERS:
        import re
        sizes = re.findall(r" gather\(.*slice_sizes=\{([0-9,]+)\}",
                           compiled.as_text())
        assert (sizes.count("1,128"), sizes.count("1")) \
            == SELECT_GATHERS[name], sizes
    if name == "fused_scan_filter_agg_q1_dense_resident":
        # 15 slots are summed by masked sums a slot and a plane: nothing
        # stacks the additive planes (a 268 MB ``f32[11, C]`` copy of a
        # 352.6 MB temporary until PR 47) and no plane is written out only
        # to be read back
        text = compiled.as_text()
        assert _wide_concatenates(text[text.index("ENTRY"):],
                                  RESIDENT_C) == []
        assert compiled.memory_analysis().temp_size_in_bytes < 100e6


@pytest.mark.parametrize("dims,inner", [((4, 2), "masked"),
                                        ((16, 4), "matmul")])
def test_q1_stacks_its_planes_only_over_the_slot_bound(dims, inner):
    """Q1's ``run_packed`` as traced (StableHLO, the CPU backend: no
    topology): at and under ``kernels.DENSE_MASKED_MAX_SLOTS`` slots no
    ``concatenate`` holds a C-wide operand; over it the one stack of the
    additive planes is there."""
    C = 524288
    prog, (out_cap, strategy, _) = _q1_program()
    assert kernels.dense_inner_loop(dims) == inner
    text = _lower_packed(jax.ShapeDtypeStruct, C, prog,
                         (out_cap, strategy, dims)).as_text()
    wide = _wide_concatenates(text, C)
    assert len(wide) == (0 if inner == "masked" else 1), wide


def test_sharded_grouped_agg_compiles_for_four_chip_mesh(topo):
    """exchange.sharded_grouped_agg (DeviceExchangeAgg's collective) on a
    Mesh of the four described devices: the compiler must place an
    all-to-all across them."""
    from daft_tpu.parallel import exchange
    assert len(topo.devices) == 4
    mesh = Mesh(np.array(topo.devices), ("data",))
    sh = NamedSharding(mesh, P("data"))
    n, C = 4, 4096

    def S(dtype):
        return jax.ShapeDtypeStruct((n * C,), dtype, sharding=sh)

    def step(k0, k1, kv0, kv1, v0, v1, vv0, vv1, m):
        return exchange.sharded_grouped_agg(
            mesh, (k0, k1), (kv0, kv1), (v0, v1), (vv0, vv1), m,
            ("sum", "sum"))

    b = S(jnp.bool_)
    lowered = jax.jit(step).lower(S(jnp.int32), S(jnp.int32), b, b,
                                  S(jnp.float32), S(jnp.float32), b, b, b)
    text = lowered.compile().as_text()
    assert "all-to-all" in text


ROUND_PROGRAMS = {"q1_dense": _q1_program, "q6": _q6_program}


@pytest.mark.parametrize("name", sorted(ROUND_PROGRAMS))
def test_round_program_holds_no_collective_on_four_chips(name, topo):
    """The fused aggregate's round launch (``FusedAggProgram.round_fn``:
    ``run_packed`` under ``shard_map`` over the four described devices,
    a table a chip at the resident cells' 4 194 304-row bucket): every
    shard reduces its own planes and the chips' partials stay apart for
    the host's float64 merge, so the compiled module holds no collective;
    and it keeps the name the device trace is read by."""
    assert len(topo.devices) == 4
    mesh = Mesh(np.array(topo.devices), ("data",))
    sh = NamedSharding(mesh, P("data"))
    prog, how = ROUND_PROGRAMS[name]()
    C = 4194304

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    text = prog.round_fn(mesh, *how).lower(
        *_packed_inputs(S, 4 * C, prog)).compile().as_text()
    head = text.splitlines()[0]
    assert head.startswith("HloModule jit_run_packed,"), head
    assert "num_partitions=4" in head
    for op in ("all-reduce", "all-gather", "collective-permute",
               "all-to-all", "reduce-scatter", "collective-broadcast"):
        assert op not in text, op
