"""The HBM column cache and the fragment dispatcher over several chips.

Four of the suite's virtual CPU devices stand for the four chips of a host
(``DAFT_TPU_MESH_DEVICES=4``): a scan task's table lives whole on one chip,
chosen by its index in the scan; the cache keeps a budget, an LRU and its
counts per chip; the window's programs run where their tables lie and their
partials are merged on the host. TPC-H Q1 and Q6 over a seeded SF0.01
``lineitem`` in 16 files, through ``read_parquet -> builder -> to_pydict``,
against the benchmark's plain float64 references and against the same
queries with one chip visible.
"""

import contextlib
import importlib

import jax
import pytest

import daft_tpu
from chipbench import answers, datagen
from daft_tpu import tracing
from daft_tpu.device import cache as dcache, column as dcol
from daft_tpu.parallel import mesh as pmesh

FILES = 16
#: floats against the float64 reference: the device sums each file in the
#: backend's widest float (f64 on these CPU devices, f32 on a TPU) and the
#: host merges the files' partials in f64 whatever chip they came from, so
#: spreading the files over chips must not cost precision; 1e-4 is the
#: benchmark's limit on the chip and far above anything seen here
RTOL = 1e-4
QUERIES = ("q1", "q6")


@pytest.fixture(scope="module")
def lineitem(tmp_path_factory):
    return datagen.ensure_dataset(
        str(tmp_path_factory.mktemp("sharded_cache")), "t", 0.01, FILES,
        ("lineitem",), 2**31 + 28, 1)


@contextlib.contextmanager
def visible_chips(n, budget=None):
    """``n`` chips visible to the scan path, the device tier forced (the
    files hold 3 750 rows), queries traced, and a cache of its own: the
    process's one keeps an (empty) share for every chip an earlier test
    of the same worker put a table on, eight in this suite."""
    mp = pytest.MonkeyPatch()
    mp.setenv("DAFT_TPU_MESH_DEVICES", str(n))
    mp.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    mp.setenv("DAFT_TPU_TRACE", "1")
    if budget is not None:
        mp.setenv("DAFT_TPU_HBM_CACHE_BYTES", str(budget))
    puts = []
    real_put = jax.device_put
    mp.setattr(jax, "device_put", lambda x, device=None, **kw: (
        puts.append(device), real_put(x, device, **kw))[1])
    mp.setattr(dcache, "_cache", dcache.DeviceColumnCache())
    pmesh.reset_for_tests()
    try:
        yield puts
    finally:
        mp.undo()
        pmesh.reset_for_tests()


def _run(root, q):
    """One query through the public path; its answer and its trace."""
    build = importlib.import_module(f"chipbench.queries.{q}").build
    got = build(lambda t: daft_tpu.read_parquet(
        f"{root}/{t}/*.parquet")).to_pydict()
    return got, tracing.finished()[-1]


def _passes(root, n):
    """Two passes of Q1 then Q6 with ``n`` chips visible: per pass and
    query the answer, the trace's summary and the cache's counts after
    it, and every ``jax.device_put`` the passes made."""
    with visible_chips(n) as puts:
        out = []
        for _ in range(2):
            one = {}
            for q in QUERIES:
                before = dcache.get_cache().stats()
                got, summary = _run(root, q)
                one[q] = {"answer": got, "summary": summary,
                          "before": before,
                          "after": dcache.get_cache().stats()}
            out.append(one)
        homes = {fp: mask[0].devices() for c in
                 dcache.get_cache()._chips.values()
                 for fp, mask in c.masks.items()}
        return {"passes": out, "puts": list(puts), "homes": homes,
                "devices": list(pmesh.scan_devices())}


@pytest.fixture(scope="module")
def four(lineitem):
    return _passes(lineitem, 4)


@pytest.fixture(scope="module")
def one(lineitem):
    return _passes(lineitem, 1)


@pytest.mark.parametrize("q", QUERIES)
def test_answers_match_the_reference_and_the_one_chip_run(lineitem, four,
                                                          one, q):
    mod = importlib.import_module(f"chipbench.reference.{q}")
    ref = mod.answer(lineitem)
    for run in (four, one):
        for p in run["passes"]:
            # keys and counts exact, floats within RTOL (see above)
            answers.compare(q, p[q]["answer"], ref, mod.COMPARE, RTOL)
    # the same per-file partials, merged by the same host code: placing
    # the files on other chips changes no bit of the answer
    assert four["passes"][1][q]["answer"] == one["passes"][1][q]["answer"]


@pytest.mark.parametrize("q", QUERIES)
def test_tables_are_spread_over_all_four_chips(four, q):
    chips = four["passes"][0][q]["summary"]["chips"]
    assert [c["chip"] for c in chips] == [0, 1, 2, 3]
    assert [c["tables"] for c in chips] == [FILES // 4] * 4
    rows = [c["rows"] for c in chips]
    assert min(rows) > 0 and (max(rows) - min(rows)) / (sum(rows) / 4) < 0.2
    assert four["passes"][0][q]["summary"]["tables"]["encoded"] == FILES


def test_every_plane_lies_on_its_tables_chip(four):
    # 16 files x 2 queries (their pushdowns differ, so their tables do)
    assert len(four["homes"]) == 2 * FILES
    on = {}
    for devices in four["homes"].values():
        (d,) = devices   # one table, one chip
        on[d] = on.get(d, 0) + 1
    assert on == {d: 2 * FILES // 4 for d in four["devices"]}
    # every upload went straight to one of the four chips (the forced
    # device tier also runs the final exchange of Q1's partial rows over
    # the mesh here: that put names a sharding, not a chip)
    assert {d for d in four["puts"] if isinstance(d, jax.Device)} == \
        set(four["devices"])
    held = four["passes"][0]["q1"]["after"]
    assert sorted(held["chips"]) == [0, 1, 2, 3]
    assert len({c["bytes"] for c in held["chips"].values()}) == 1
    assert sum(c["bytes"] for c in held["chips"].values()) == held["bytes"]


@pytest.mark.parametrize("q", QUERIES)
def test_second_pass_finds_every_table_where_the_first_put_it(four, q):
    first, second = (p[q] for p in four["passes"])
    assert second["summary"]["tables"] == {
        "from_cache": FILES, "encoded": 0, "host": 0}
    assert second["after"]["hits"] - second["before"]["hits"] == FILES
    assert second["after"]["misses"] == second["before"]["misses"]
    assert second["after"]["put_bytes"] == second["before"]["put_bytes"]
    # no table is put again (the forced device tier also merges the
    # files' few partial rows on the device: those bytes are the rest)
    put = second["summary"]["phases"].get("device:put", {})
    assert put.get("bytes", 0) < first["summary"]["phases"][
        "device:put"]["bytes"] // FILES     # less than one table's
    # the same tables, the same rows, on the same chips
    for a, b in zip(first["summary"]["chips"], second["summary"]["chips"]):
        assert (a["chip"], a["tables"], a["rows"]) == \
            (b["chip"], b["tables"], b["rows"])
    assert second["summary"]["chips"][0]["resident_bytes"] == \
        second["after"]["chips"][0]["bytes"] > 0


def test_one_visible_chip_places_nothing_and_puts_nothing_more(one, four):
    assert one["puts"] == []          # jnp.asarray, as before: no device_put
    assert len(one["devices"]) == 1
    first = one["passes"][0]["q1"]
    assert [(c["chip"], c["tables"]) for c in first["summary"]["chips"]] \
        == [(0, FILES)]
    assert [k for k, c in first["after"]["chips"].items() if c["bytes"]] \
        == [0]
    # as many puts, of as many bytes, as over four chips
    for q in QUERIES:
        a = one["passes"][0][q]["summary"]["phases"]["device:put"]
        b = four["passes"][0][q]["summary"]["phases"]["device:put"]
        assert (a["count"], a["bytes"]) == (b["count"], b["bytes"])
    with visible_chips(1):
        assert dcol.encode_batch(_tiny_batch()).chip is None


def _tiny_batch():
    from daft_tpu.recordbatch import RecordBatch
    return RecordBatch.from_pydict({"x": [1.0, 2.0, 3.0]})


@pytest.mark.parametrize("chips,cached", [(1, 0), (2, 0), (4, FILES)])
def test_fits_scales_with_the_chips(lineitem, four, chips, cached):
    """A budget that a chip's share of four fits (4 tables) and the whole
    scan (16) or a half of it (8) does not: the gate's "the whole scan
    fits" is the fullest chip's share against one chip's budget."""
    per_table = four["passes"][0]["q1"]["after"]["bytes"] // FILES
    with visible_chips(chips, budget=5 * per_table):
        _, summary = _run(lineitem, "q1")
        stats = dcache.get_cache().stats()
        assert summary["tables"]["encoded"] == FILES
        assert stats["bytes"] == cached * per_table
        assert stats["evicted_bytes"] == 0
        if cached:
            assert {k: c["bytes"] for k, c in stats["chips"].items()} == \
                {k: 4 * per_table for k in range(4)}
        _, again = _run(lineitem, "q1")
        assert again["tables"]["from_cache"] == cached


def test_budget_lru_and_eviction_are_per_chip():
    """Filling one chip evicts that chip's oldest table and no other's."""
    cache = dcache.DeviceColumnCache()
    with visible_chips(4):
        tables = [dcol.encode_batch(_tiny_batch(), chip=k % 2)
                  for k in range(5)]
        per_table = sum(int(c.data.nbytes) + int(c.validity.nbytes)
                        for c in tables[0].columns.values())
        mp = pytest.MonkeyPatch()   # the budget is read at every put
        mp.setenv("DAFT_TPU_HBM_CACHE_BYTES", str(2 * per_table))
        try:
            for k, dt in enumerate(tables):   # chips 0 1 0 1 0
                cache.put_table(("fp", k), dt)
            stats = cache.stats()
            assert stats["chips"] == {
                0: {"entries": 2, "bytes": 2 * per_table,
                    "evicted_bytes": per_table},
                1: {"entries": 2, "bytes": 2 * per_table,
                    "evicted_bytes": 0}}
            assert stats["bytes"] == 4 * per_table
            assert stats["evicted_bytes"] == per_table
            assert cache.get_table(("fp", 0), ["x"]) is None   # chip 0's LRU
            assert cache.home(("fp", 0)) is None
            for k in (1, 2, 3, 4):
                got = cache.get_table(("fp", k), ["x"])
                assert got.chip == k % 2 == cache.home(("fp", k))
                assert got.resident
                assert got.row_mask.devices() == \
                    {pmesh.scan_devices()[k % 2]}
            # a table put again elsewhere moves whole: never split
            moved = dcol.encode_batch(_tiny_batch(), chip=3)
            cache.put_table(("fp", 1), moved)
            assert cache.home(("fp", 1)) == 3
            assert cache.stats()["chips"][1]["entries"] == 1
            # a table larger than one chip's budget is refused
            mp.setenv("DAFT_TPU_HBM_CACHE_BYTES", str(per_table - 1))
            cache.put_table(("fp", 9), tables[0])
            assert cache.home(("fp", 9)) is None
        finally:
            mp.undo()


def test_clear_empties_every_chip(lineitem):
    with visible_chips(4):
        _run(lineitem, "q1")
        cache = dcache.get_cache()
        assert all(c["bytes"] > 0 for c in cache.stats()["chips"].values())
        assert len(cache.stats()["chips"]) == 4
        cache.clear()
        stats = cache.stats()
        assert stats["bytes"] == stats["entries"] == 0
        assert all(c == {"entries": 0, "bytes": 0,
                         "evicted_bytes": c["evicted_bytes"]}
                   for c in stats["chips"].values())
        _, summary = _run(lineitem, "q1")   # and the next scan re-encodes
        assert summary["tables"]["encoded"] == FILES


def test_the_spans_carry_the_chip(lineitem):
    from daft_tpu import observability as obs
    with visible_chips(4):
        _run(lineitem, "q1")
        spans = obs.last_query_stats().trace_ctx.recorder.spans()
    # a launch a round of four tables, one a chip (PR 44): the span of a
    # round carries its tables and chips, not one chip
    rounds = [s["attrs"] for s in spans if s["name"] == "device:dispatch"
              and s["attrs"].get("strategy") != "plan"]
    assert [(a["tables"], a["chips"], "chip" in a) for a in rounds] == \
        [(4, 4, False)] * (FILES // 4)
    launches = [s["attrs"] for s in spans if s["name"] == "dispatch:launch"
                and s["attrs"]["program"].startswith("fragment.")]
    assert [(a["program"], a["tables"], a["chips"]) for a in launches] == \
        [("fragment.round", 4, 4)] * (FILES // 4)
    puts = [s["attrs"]["chip"] for s in spans if s["name"] == "device:put"]
    assert set(puts) == {0, 1, 2, 3}
    fetches = [s["attrs"] for s in spans if s["name"] == "device:fetch"]
    assert fetches and all("chips" in a for a in fetches)
    assert max(a["chips"] for a in fetches) == 4
