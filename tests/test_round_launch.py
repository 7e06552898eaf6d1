"""One launch for a round of chips (PR 44): where a window's resident
tables lie on several chips, the fused aggregate takes them chip by chip
and launches the j-th table of every chip as ONE SPMD program whose shards
are the planes the tables already hold (``fragment._dispatch_round``); a
ragged round, a failed round launch and every window on one chip take the
launch a table. Four of the suite's virtual CPU devices stand for the four
chips (``DAFT_TPU_MESH_DEVICES=4``), as in ``tests/test_sharded_cache.py``.
"""

import contextlib
import importlib

import jax
import numpy as np
import pytest

import daft_tpu
from daft_tpu import col, tracing
from daft_tpu.aggs import split_agg_expr
from daft_tpu.device import cache as dcache, column as dcol, costmodel
from daft_tpu.device import fragment, runtime
from daft_tpu.parallel import mesh as pmesh
from daft_tpu.recordbatch import RecordBatch

CHIPS = 4
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "all-to-all", "reduce-scatter")


@contextlib.contextmanager
def visible_chips(n):
    mp = pytest.MonkeyPatch()
    mp.setenv("DAFT_TPU_MESH_DEVICES", str(n))
    mp.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    mp.setenv("DAFT_TPU_TRACE", "1")
    mp.setattr(dcache, "_cache", dcache.DeviceColumnCache())
    pmesh.reset_for_tests()
    try:
        yield mp
    finally:
        mp.undo()
        pmesh.reset_for_tests()


def _bits(series):
    """A column as (arrow type, validity, value bits)."""
    import pyarrow as pa
    import pyarrow.compute as pc
    arr = series.to_arrow()
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    valid = pc.is_valid(arr).to_pylist()
    if pa.types.is_floating(arr.type):
        vals = np.asarray(pc.fill_null(arr, 0.0)).view(
            np.uint64 if arr.type == pa.float64() else np.uint32).tolist()
    else:
        vals = arr.to_pylist()
    return arr.type, valid, vals


def _runs_bits(runs):
    """A window's ``DecodedRun`` s as comparable values: the tables of
    each and its batch column by column, bit for bit."""
    return [(r.tables, None if r.batch is None else
             [(c.name(), *_bits(c)) for c in r.batch.columns()])
            for r in runs]


def _keyed(keys, n, rng):
    # every table holds every key: equal dictionaries, each its own
    return [keys[i] for i in rng.integers(0, len(keys), n)] + list(keys)


def _window(case, n_tables):
    """(tables as pydicts, group keys, aggs, predicate, strategy)."""
    rng = np.random.default_rng(44)

    def vals(n):
        return (rng.random(n) * 100).tolist()

    q1_aggs = [col("qty").sum().alias("sum_qty"),
               (col("price") * (1 - col("disc"))).sum().alias("sum_disc"),
               col("qty").count().alias("n_qty"),
               col("price").min().alias("min_price")]
    if case == "q1_dense":
        return ([{"flag": _keyed("ANR", 600, rng),
                  "status": _keyed("FO", 601, rng),
                  "qty": vals(603), "price": vals(603), "disc": vals(603)}
                 for _ in range(n_tables)],
                ["flag", "status"], q1_aggs, None, "dense")
    if case == "q6_global":
        return ([{"qty": vals(400), "price": vals(400),
                  "disc": (rng.random(400) * 0.1).tolist()}
                 for _ in range(n_tables)], [],
                [(col("price") * col("disc")).sum().alias("revenue"),
                 col("qty").count().alias("n")], col("qty") < 24, "sort")
    if case == "sort_groups":
        return ([{"k": rng.integers(0, 3 + 11 * s, 500).tolist(),
                  "qty": vals(500), "price": vals(500), "disc": vals(500)}
                 for s in range(n_tables)], ["k"], q1_aggs, None, "sort")
    if case == "overflow_retried":
        # tables 1 and 6 outgrow the 128-group bucket of their rounds:
        # each re-runs alone at its grown bucket
        return ([{"k": (np.arange(900) % ndv).tolist(), "qty": vals(900),
                  "price": vals(900), "disc": vals(900)}
                 for ndv in ([5, 300, 7, 9, 2, 3, 200, 11] * 2)[:n_tables]],
                ["k"], q1_aggs, None, "sort")
    if case == "dims_differ":
        # the first round's tables hold three flags (dims 4), but for
        # chip 2's, which holds two (dims 2): no one program fits them
        return ([{"flag": _keyed("AN" if s == 2 else "ANR", 300, rng),
                  "qty": vals(302 if s == 2 else 303),
                  "price": vals(302 if s == 2 else 303),
                  "disc": vals(302 if s == 2 else 303)}
                 for s in range(n_tables)], ["flag"], q1_aggs, None, "dense")
    raise AssertionError(case)


def _encoded(case, chips):
    """The case's program and its tables, table ``i`` on ``chips[i]``."""
    data, keys, aggs, pred, strategy = _window(case, len(chips))
    rbs = [RecordBatch.from_pydict(d) for d in data]
    gexprs = [col(k) for k in keys]
    specs = [split_agg_expr(a) for a in aggs]
    prog = fragment.get_fused_agg(
        gexprs, [s[1].alias(f"__v{i}__") for i, s in enumerate(specs)],
        tuple(s[0] for s in specs), pred, rbs[0].schema)
    assert prog is not None
    out_schema = (rbs[0].filter(pred) if pred is not None else rbs[0]) \
        .agg(aggs, gexprs).schema
    tables = [dcol.encode_batch(rb, prog.compiled.needs_cols, chip=k)
              for rb, k in zip(rbs, chips)]
    args = (rbs[0].schema, gexprs, [col(s[2]) for s in specs], out_schema)
    return prog, tables, args, strategy


def _answer(prog, tables, args):
    """The window through submit + drain under a trace of its own: its
    runs, the launches the kernel ledger counted, the trace's tally."""
    rec = tracing.SpanRecorder("e" * 32)
    before = costmodel.ledger_snapshot()
    with tracing.attach(tracing.SpanContext(rec, rec.root_id)):
        tok = fragment.submit_fused_agg_tables(prog, tables, *args)
        assert not tok.failed
        runs = fragment.drain_fused_agg_tables(tok)
    rec.finish("ok")
    after = costmodel.ledger_snapshot()
    kind = "grouped_agg" if prog.nk else "global_agg"
    launches = after[kind]["dispatches"] \
        - before.get(kind, {}).get("dispatches", 0)
    return runs, launches, rec.summary()["agg_launches"], tok, rec.spans()


def _per_table(mp, prog, tables, args):
    """The same window by the launch a table, which stays the reference."""
    with mp.context() as m:
        m.setattr(fragment, "_launches", lambda prog, tables, hows: None)
        return _answer(prog, tables, args)


@pytest.mark.parametrize("case", ["q1_dense", "q6_global", "sort_groups",
                                  "overflow_retried"])
def test_a_window_over_four_chips_is_a_launch_a_round(monkeypatch, case):
    """Eight resident tables, two a chip: two launches, and the partial
    batches of the launch a table (same rows, same order, same runs)."""
    chips = [0, 1, 2, 3] * 2
    with visible_chips(CHIPS):
        prog, tables, args, strategy = _encoded(case, chips)
        want, n_single, tally_single, _, _ = _per_table(
            monkeypatch, prog, tables, args)
        got, n_round, tally_round, tok, spans = _answer(prog, tables, args)
    assert tok.strategy == strategy
    assert _runs_bits(got) == _runs_bits(want)
    assert sum(r.tables for r in got) == len(tables)
    retried = 2 if case == "overflow_retried" else 0
    # ceil(tables / chips) launches, by the ledger and by the tally
    assert n_round == -(-len(tables) // CHIPS)
    assert tok.cuts == [(0, 1, 2, 3), (4, 5, 6, 7)]
    assert n_single == len(tables)
    assert tally_round == {"tables_round": len(tables), "tables_single": 0}
    assert tally_single == {"tables_round": 0,
                            "tables_single": len(tables)}
    rounds = [s["attrs"] for s in spans if s["name"] == "device:dispatch"
              and s["attrs"].get("strategy") != "plan"]
    assert [(a.get("tables"), a.get("chips")) for a in rounds] == \
        [(CHIPS, CHIPS)] * 2 + [(None, None)] * retried
    launched = [s["attrs"] for s in spans if s["name"] == "dispatch:launch"]
    assert [(a["program"], a.get("tables"), a.get("chips"))
            for a in launched] == \
        [("fragment.round", CHIPS, CHIPS)] * 2 \
        + [("fragment.packed", None, None)] * retried


@pytest.mark.parametrize("case,inner", [("q1_dense", "masked"),
                                        ("sort_groups", None)])
@pytest.mark.parametrize("launch", ["round", "table"])
def test_a_dense_launch_says_which_inner_loop_it_takes(
        monkeypatch, tmp_path, case, inner, launch):
    """The dense aggregate's inner loop (``kernels.dense_inner_loop`` of
    the table's ``dims``: Q1's six slots are summed ``masked``) is on the
    ``device:dispatch`` span of every dense launch, a round's and a
    table's, and on the window's strategy decision; a sort launch says
    nothing of it."""
    import json
    log = tmp_path / "decisions.jsonl"
    with visible_chips(CHIPS) as mp:
        mp.setenv("DAFT_TPU_DISPATCH_LOG", str(log))
        prog, tables, args, strategy = _encoded(case, [0, 1, 2, 3])
        log.write_text("")   # what making the case decided is not asked
        if launch == "table":
            mp.setattr(fragment, "_launches", lambda prog, tables, hows: None)
        _, n, _, tok, spans = _answer(prog, tables, args)
    assert (tok.strategy, tok.inner) == (strategy, inner)
    assert n == (1 if launch == "round" else CHIPS)
    launched = [s["attrs"] for s in spans if s["name"] == "device:dispatch"
                and s["attrs"].get("strategy") != "plan"]
    assert [(a["strategy"], a.get("inner")) for a in launched] \
        == [(strategy, inner)] * n
    decided = [json.loads(ln) for ln in log.read_text().splitlines()]
    assert [(d["strategy"], d.get("inner")) for d in decided
            if d["kind"] == "groupby_strategy"] == [(strategy, inner)]


@pytest.mark.parametrize("case,chips,cuts", [
    # chip 3 is a table short: its last round is ragged
    ("q1_dense", [0, 1, 2, 3, 0, 1, 2],
     [(0, 1, 2, 3), (4,), (5,), (6,)]),
    # a cache miss was placed on chip 2, where another of the window's
    # tables lies already: chip 3 has one table, chip 2 three
    ("q6_global", [0, 1, 2, 2, 0, 1, 2, 3],
     [(0, 1, 2, 7), (4,), (5,), (3,), (6,)]),
    # every table on one chip of the four: nothing to launch together
    ("sort_groups", [2, 2, 2, 2, 2], None),
    # a round whose tables do not agree in their dims
    ("dims_differ", [0, 1, 2, 3, 0, 1, 2, 3],
     [(0,), (1,), (2,), (3,), (4, 5, 6, 7)]),
])
def test_a_ragged_round_is_launched_table_by_table(monkeypatch, case, chips,
                                                   cuts):
    with visible_chips(CHIPS):
        prog, tables, args, _ = _encoded(case, chips)
        want, n_single, _, _, _ = _per_table(monkeypatch, prog, tables, args)
        got, n, tally, tok, _ = _answer(prog, tables, args)
    assert tok.cuts == cuts
    assert _runs_bits(got) == _runs_bits(want)
    in_rounds = sum(len(c) for c in cuts or () if len(c) > 1)
    assert n == (len(cuts) if cuts else len(tables))
    assert n_single == len(tables)
    assert tally == {"tables_round": in_rounds,
                     "tables_single": len(tables) - in_rounds}


def test_a_failed_round_launch_falls_back_table_by_table(monkeypatch):
    """Resource exhaustion at a round's launch is counted and its tables
    are launched one by one; anything else fails the query."""
    with visible_chips(CHIPS):
        prog, tables, args, _ = _encoded("q1_dense", [0, 1, 2, 3] * 2)
        want, _, _, _, _ = _per_table(monkeypatch, prog, tables, args)
        real, calls = fragment._dispatch_round, []

        def second_fails(*a, **k):
            calls.append(1)
            if len(calls) == 2:
                raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory "
                                   "while trying to allocate 1 bytes.")
            return real(*a, **k)

        costmodel.reset_for_tests()
        monkeypatch.setattr(fragment, "_dispatch_round", second_fails)
        got, n, tally, tok, _ = _answer(prog, tables, args)
        fails = runtime.device_failures()
        costmodel.reset_for_tests()
        monkeypatch.setattr(fragment, "_dispatch_round", lambda *a, **k:
                            (_ for _ in ()).throw(TypeError("bad trace")))
        with pytest.raises(TypeError):
            fragment.submit_fused_agg_tables(prog, tables, *args)
    assert tok.cuts == [(0, 1, 2, 3), (4,), (5,), (6,), (7,)]
    assert _runs_bits(got) == _runs_bits(want)
    assert n == 5 and tally == {"tables_round": 4, "tables_single": 4}
    assert list(fails) == ["fragment.fused_agg_tables.round"]
    assert fails["fragment.fused_agg_tables.round"]["count"] == 1


def test_one_visible_chip_builds_no_global_array(monkeypatch):
    """One chip: ``chip`` is None, a launch a table, and nothing of the
    round path is touched."""
    with visible_chips(1) as mp:
        mp.setattr(jax, "make_array_from_single_device_arrays",
                   lambda *a, **k: pytest.fail("a global array on one chip"))
        mp.setattr(fragment, "_dispatch_round", lambda *a, **k: pytest.fail(
            "a round launch on one chip"))
        prog, tables, args, _ = _encoded("q1_dense", [None] * 6)
        assert all(dt.chip is None for dt in tables)
        runs, n, tally, tok, spans = _answer(prog, tables, args)
    assert tok.cuts is None and len(tok.packs) == len(tables) == n
    assert tally == {"tables_round": 0, "tables_single": len(tables)}
    assert [r.tables for r in runs] == [len(tables)]
    assert all(s["attrs"]["program"] == "fragment.packed"
               for s in spans if s["name"] == "dispatch:launch")


def test_a_rounds_inputs_are_the_tables_own_buffers(monkeypatch):
    """Every input plane of a round is the buffer its table holds (the
    HBM cache's, for a resident table): no copy, and the program's
    module keeps the name the device trace is read by."""
    seen = []
    real = fragment.FusedAggProgram.round_fn

    def spy(self, mesh, out_cap, strategy, dims):
        fn = real(self, mesh, out_cap, strategy, dims)

        def call(arrays, valids, row_mask, scalars):
            seen.append((fn, arrays, valids, row_mask, scalars))
            return fn(arrays, valids, row_mask, scalars)
        return call

    with visible_chips(CHIPS):
        prog, tables, args, _ = _encoded("q1_dense", [0, 1, 2, 3])
        cache = dcache.get_cache()
        for k, dt in enumerate(tables):
            cache.put_table(("fp", k), dt)
        tables = [cache.get_table(("fp", k), prog.compiled.needs_cols)
                  for k in range(CHIPS)]
        assert all(dt.resident and dt.chip == k
                   for k, dt in enumerate(tables))
        monkeypatch.setattr(fragment.FusedAggProgram, "round_fn", spy)
        _answer(prog, tables, args)
        (fn, arrays, valids, row_mask, scalars), = seen
        devices = pmesh.scan_devices()

        def same(whole, planes):
            assert whole.shape == (CHIPS * planes[0].shape[0],) \
                + planes[0].shape[1:]
            shards = sorted(whole.addressable_shards,
                            key=lambda s: devices.index(s.device))
            assert [s.device for s in shards] == devices
            assert [s.data.unsafe_buffer_pointer() for s in shards] == \
                [p.unsafe_buffer_pointer() for p in planes]

        for nm in prog.compiled.needs_cols:
            same(arrays[nm], [dt.columns[nm].data for dt in tables])
            same(valids[nm], [dt.columns[nm].validity for dt in tables])
        same(row_mask, [dt.row_mask for dt in tables])
        assert scalars == ()
        text = fn.lower(arrays, valids, row_mask, scalars).compile() \
            .as_text()
    assert text.startswith("HloModule jit_run_packed,")
    assert "num_partitions=4" in text.splitlines()[0]
    assert not [c for c in COLLECTIVES if c in text]


def _resident(prog, tables):
    """``tables`` put on the HBM cache and taken back from it."""
    cache = dcache.get_cache()
    for k, dt in enumerate(tables):
        cache.put_table(("fp", k), dt)
    got = [cache.get_table(("fp", k), prog.compiled.needs_cols)
           for k in range(len(tables))]
    assert all(dt is not None and dt.resident for dt in got)
    return got


def _assembled(spans):
    return [s["attrs"]["assembled"] for s in spans
            if s["name"] == "device:dispatch" and "assembled" in s["attrs"]]


def test_a_resident_rounds_inputs_are_assembled_once(monkeypatch):
    """The global arrays over a round's resident planes are kept on the
    cache beside them: the next query's round launches over the SAME
    arrays and assembles nothing; tables that are not the cache's keep
    nothing; the answers are the launch a table's either way."""
    made = []
    real = jax.make_array_from_single_device_arrays

    def counting(*a, **k):
        made.append(1)
        return real(*a, **k)

    with visible_chips(CHIPS) as mp:
        prog, fresh, args, _ = _encoded("q1_dense", [0, 1, 2, 3] * 2)
        want, _, _, _, _ = _per_table(monkeypatch, prog, fresh, args)
        mp.setattr(jax, "make_array_from_single_device_arrays", counting)
        _, _, _, _, spans = _answer(prog, fresh, args)
        assert _assembled(spans) == [1, 1] and len(made) == 2 * 11
        assert dcache.get_cache().stats()["rounds"] == 0
        tables = _resident(prog, fresh)
        del made[:]
        first, _, _, _, spans = _answer(prog, tables, args)
        assert _assembled(spans) == [1, 1] and len(made) == 2 * 11
        assert dcache.get_cache().stats()["rounds"] == 2
        # another query: new DeviceTables over the cache's own planes
        tables = [dcache.get_cache().get_table(("fp", k),
                                               prog.compiled.needs_cols)
                  for k in range(len(tables))]
        again, n, tally, _, spans = _answer(prog, tables, args)
        assert _assembled(spans) == [0, 0] and len(made) == 2 * 11
    assert n == 2 and tally == {"tables_round": 8, "tables_single": 0}
    assert _runs_bits(first) == _runs_bits(again) == _runs_bits(want)


@pytest.mark.parametrize("how", ["evicted", "put_again", "further_columns",
                                 "moved", "cleared"])
def test_assembled_inputs_go_when_a_plane_leaves_the_cache(monkeypatch, how):
    """A global array holds its shards' buffers: when any plane leaves the
    cache (evicted by the budget, replaced by a new put, its mask alone
    replaced by a put of further columns, its table put on another chip,
    the cache cleared) every kept round goes with it, and the
    next launch assembles what the cache holds then."""
    with visible_chips(CHIPS) as mp:
        prog, fresh, args, _ = _encoded("q1_dense", [0, 1, 2, 3] * 2)
        tables = _resident(prog, fresh)
        want, _, _, _, _ = _answer(prog, tables, args)
        cache = dcache.get_cache()
        assert cache.stats()["rounds"] == 2
        if how == "evicted":
            # chip 1 holds two tables; a budget of one evicts the older
            one = cache.stats()["chips"][1]["bytes"] // 2
            mp.setattr(dcache, "_budget", lambda: one)
            cache.put_table(("fp", 5), fresh[5])
            assert cache.stats()["evicted_bytes"] > 0
            assert cache.get_table(("fp", 1),
                                   prog.compiled.needs_cols) is None
        elif how == "put_again":
            _, again, _, _ = _encoded("q1_dense", [0, 1, 2, 3] * 2)
            cache.put_table(("fp", 6), again[6])
        elif how == "further_columns":
            # a later scan's put of the same file: no column of the kept
            # round is replaced, but the table's row mask is
            _, again, _, _ = _encoded("q1_dense", [0, 1, 2, 3] * 2)
            held = cache.stats()["entries"]
            cache.put_table(("fp", 6), dcol.DeviceTable(
                {"tax": again[6].columns["qty"]}, again[6].row_mask,
                again[6].row_count, again[6].capacity, chip=again[6].chip))
            assert cache.stats()["entries"] == held + 1
        elif how == "moved":
            _, again, _, _ = _encoded("q1_dense", [1] * 8)
            cache.put_table(("fp", 4), again[4])
            assert cache.home(("fp", 4)) == 1
        else:
            cache.clear()
        assert cache.stats()["rounds"] == 0
        if how in ("put_again", "further_columns"):
            tables = [cache.get_table(("fp", k), prog.compiled.needs_cols)
                      for k in range(8)]
            got, n, _, _, spans = _answer(prog, tables, args)
            assert n == 2 and _assembled(spans) == [1, 1]
            assert _runs_bits(got) == _runs_bits(want)
            assert cache.stats()["rounds"] == 2


def test_a_rounds_scalars_are_each_tables_own(monkeypatch):
    """A runtime scalar is a function of each table's OWN dictionary (the
    rank of a literal in it): a round carries the chips' side by side."""
    rng = np.random.default_rng(3)
    chips = [0, 1, 2, 3] * 2
    # 'N' ranks 1 among A N R, 0 among N R, 2 among A M N
    letters = ["ANR", "NR", "AMN", "ANR"] * 2
    rbs = [RecordBatch.from_pydict({
        "flag": _keyed(keys, 300, rng),
        "qty": (rng.random(300 + len(keys)) * 50).tolist()})
        for keys in letters]
    aggs = [col("qty").sum().alias("s"), col("qty").count().alias("n")]
    specs = [split_agg_expr(a) for a in aggs]
    pred = col("flag") == "N"
    with visible_chips(CHIPS):
        prog = fragment.get_fused_agg(
            [], [s[1].alias(f"__v{i}__") for i, s in enumerate(specs)],
            tuple(s[0] for s in specs), pred, rbs[0].schema)
        assert prog is not None and prog.compiled.scalar_specs
        tables = [dcol.encode_batch(rb, prog.compiled.needs_cols, chip=k)
                  for rb, k in zip(rbs, chips)]
        args = (rbs[0].schema, [], [col(s[2]) for s in specs],
                rbs[0].filter(pred).agg(aggs, []).schema)
        want, _, _, _, _ = _per_table(monkeypatch, prog, tables, args)
        got, n, tally, tok, _ = _answer(prog, tables, args)
    assert n == 2 and tally["tables_round"] == 8
    assert _runs_bits(got) == _runs_bits(want)
    counts = got[0].batch.to_pydict()["n"]
    assert counts == [rb.filter(pred).agg(aggs, []).to_pydict()["n"][0]
                      for rb in rbs]


def test_a_round_whose_scalars_differ_in_shape_is_launched_table_by_table(
        monkeypatch):
    """The runtime scalars' shapes are part of what a round agrees in: a
    table whose plane is longer (here: padded with a code no row holds)
    is no shard of its round's program, and the round falls back."""
    rng = np.random.default_rng(5)
    rbs = [RecordBatch.from_pydict({
        "flag": _keyed("ANR", 300, rng),
        "qty": (rng.random(303) * 50).tolist()}) for _ in range(8)]
    aggs = [col("qty").sum().alias("s"), col("qty").count().alias("n")]
    specs = [split_agg_expr(a) for a in aggs]
    pred = col("flag").is_in(["N", "R"])
    real = runtime._scalar_planes
    with visible_chips(CHIPS) as mp:
        prog = fragment.get_fused_agg(
            [], [s[1].alias(f"__v{i}__") for i, s in enumerate(specs)],
            tuple(s[0] for s in specs), pred, rbs[0].schema)
        assert prog is not None and prog.compiled.scalar_specs
        tables = [dcol.encode_batch(rb, prog.compiled.needs_cols, chip=k)
                  for rb, k in zip(rbs, [0, 1, 2, 3] * 2)]
        mp.setattr(runtime, "_scalar_planes", lambda c, dt: [
            np.append(x, np.int32(-1)) if dt is tables[2] else x
            for x in real(c, dt)])
        args = (rbs[0].schema, [], [col(s[2]) for s in specs],
                rbs[0].filter(pred).agg(aggs, []).schema)
        want, _, _, _, _ = _per_table(monkeypatch, prog, tables, args)
        got, n, tally, tok, _ = _answer(prog, tables, args)
    assert tok.cuts == [(0,), (1,), (2,), (3,), (4, 5, 6, 7)]
    assert n == 5 and tally == {"tables_round": 4, "tables_single": 4}
    assert _runs_bits(got) == _runs_bits(want)
    assert got[0].batch.to_pydict()["n"] == [
        rb.filter(pred).agg(aggs, []).to_pydict()["n"][0] for rb in rbs]


# ------------------------------------------------- through the public path

FILES = 16
QUERIES = ("q1", "q6")


@pytest.fixture(scope="module")
def lineitem(tmp_path_factory):
    from chipbench import datagen
    return datagen.ensure_dataset(
        str(tmp_path_factory.mktemp("round_launch")), "t", 0.01, FILES,
        ("lineitem",), 2**31 + 44, 1)


def _query(root, q):
    build = importlib.import_module(f"chipbench.queries.{q}").build
    before = costmodel.ledger_snapshot()
    got = build(lambda t: daft_tpu.read_parquet(
        f"{root}/{t}/*.parquet")).to_pydict()
    after = costmodel.ledger_snapshot()
    kind = "grouped_agg" if q == "q1" else "global_agg"
    return got, tracing.finished()[-1], after[kind]["dispatches"] \
        - before.get(kind, {}).get("dispatches", 0)


@pytest.mark.parametrize("q", QUERIES)
def test_a_resident_scan_answers_the_same_in_fewer_launches(lineitem, q,
                                                            monkeypatch):
    """Q1 and Q6 over 16 resident files on four chips, through
    ``read_parquet -> builder -> to_pydict``: the answer of the launch a
    table, bit for bit, and a launch a round where the rounds are whole
    (Q6's filtered tables straddle two capacity buckets at this size:
    some of its rounds are ragged, and the tally says how many)."""
    with visible_chips(CHIPS):
        _query(lineitem, q)                      # fills the cache
        got, summary, n = _query(lineitem, q)
        with monkeypatch.context() as m:
            m.setattr(fragment, "_launches", lambda prog, tables, hows: None)
            want, single, n_single = _query(lineitem, q)
    assert summary["tables"]["from_cache"] == FILES
    assert got == want
    tally = summary["agg_launches"]
    assert tally["tables_round"] + tally["tables_single"] == FILES
    assert single["agg_launches"] == {"tables_round": 0,
                                      "tables_single": FILES}
    # the windows are a multiple of the chips wide, so every round is whole
    # where the tables agree; a round saves chips - 1 launches
    assert tally["tables_round"] % CHIPS == 0
    assert n_single - n == (CHIPS - 1) * tally["tables_round"] // CHIPS
    if q == "q1":
        assert tally == {"tables_round": FILES, "tables_single": 0}
    else:
        assert tally["tables_round"] >= CHIPS


def test_windows_are_a_multiple_of_the_chips_wide(lineitem):
    """16 tasks, three windows wanted: 8 + 8 and not 6 + 6 + 4, so that no
    round is ragged by the window's cut."""
    with visible_chips(CHIPS):
        _query(lineitem, "q1")
        _, summary, _ = _query(lineitem, "q1")
    assert summary["decode"] == {"tables": FILES, "batches": 2}
    with visible_chips(1):
        _query(lineitem, "q1")
        _, summary, _ = _query(lineitem, "q1")
    assert summary["decode"] == {"tables": FILES, "batches": 3}
