"""What the deployment ``tpch-sf10-all`` (cell ``tpch-sf10.join``) leans on,
small and on the CPU: TPC-H Q3 and Q10 over 16-file tables through
``daft_tpu.read_parquet`` against the benchmark's plain reference with the
fused device join forced on, forced off and left to the gate; the ``joins``
tally and the ``join:device`` span a traced query keeps; and what the join
gate (``costmodel.join_wins``) answers at the bucket-pair sizes of SF1 and
SF10 under the link the chip showed."""

import importlib
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import daft_tpu  # noqa: E402
from chipbench import answers, datagen  # noqa: E402
from daft_tpu import joins, observability as obs, tracing  # noqa: E402
from daft_tpu.device import costmodel as cm  # noqa: E402

QUERIES = ("q3", "q10")
#: ``DAFT_TPU_DEVICE_JOIN`` for each way a bucket pair can be matched
MODES = {"forced-on": "1", "forced-off": "0", "auto": None}
RTOL = 1e-4


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return datagen.ensure_dataset(
        str(tmp_path_factory.mktemp("tpch_sf002")), "t", 0.02, 16,
        datagen.ALL_TABLES, 2**31 + 38, 1)


@pytest.fixture(scope="module")
def runs(root):
    """Every query in every mode, traced: ``{(query, mode): (answer, the
    trace's summary, its spans, calls of match_indices)}``."""
    mp = pytest.MonkeyPatch()
    calls = []
    real = joins.match_indices

    def counted(*args):
        calls.append(1)
        return real(*args)

    mp.setattr(joins, "match_indices", counted)
    mp.setenv("DAFT_TPU_TRACE", "1")
    mp.delenv("DAFT_TPU_DEVICE", raising=False)
    out = {}
    try:
        for q in QUERIES:
            build = importlib.import_module(f"chipbench.queries.{q}").build
            for mode, env in MODES.items():
                if env is None:
                    mp.delenv("DAFT_TPU_DEVICE_JOIN", raising=False)
                else:
                    mp.setenv("DAFT_TPU_DEVICE_JOIN", env)
                tracing.reset_for_tests()
                del calls[:]
                got = build(lambda t: daft_tpu.read_parquet(
                    f"{root}/{t}/*.parquet")).to_pydict()
                spans = obs.last_query_stats().trace_ctx.recorder.spans()
                (summary,) = tracing.finished()
                out[q, mode] = (got, summary, spans, len(calls))
    finally:
        mp.undo()
        tracing.reset_for_tests()
    return out


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("q", QUERIES)
def test_answer_is_the_references(root, runs, q, mode):
    ref = importlib.import_module(f"chipbench.reference.{q}")
    err = answers.compare(f"{q} {mode}", runs[q, mode][0], ref.answer(root),
                          ref.COMPARE, RTOL)
    assert err <= RTOL


@pytest.mark.parametrize("q", QUERIES)
def test_answers_are_equal_across_the_modes(runs, q):
    first = runs[q, "forced-off"][0]
    for mode in ("forced-on", "auto"):
        got = runs[q, mode][0]
        assert list(got) == list(first)
        for name in first:
            assert got[name] == pytest.approx(first[name], rel=1e-9), \
                (mode, name)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("q", QUERIES)
def test_the_tally_adds_up_to_the_calls(runs, q, mode):
    _, summary, spans, calls = runs[q, mode]
    j = summary["joins"]
    assert set(j) == set(tracing.JOIN_TALLIES)
    assert calls > 0 and j["pairs_device"] + j["pairs_host"] == calls
    device = [s for s in spans if s["name"] == "join:device"]
    host = [s for s in spans if s["name"] == "join:build"
            and s["attrs"]["step"] == "sort"]
    assert (len(device), len(host)) == (j["pairs_device"], j["pairs_host"])
    for tier, group in (("device", device), ("host", host)):
        assert j[f"rows_{tier}"] == sum(
            s["attrs"]["rows_left"] + s["attrs"]["rows_right"]
            for s in group)
    assert j["max_pair_rows"] == max(
        s["attrs"]["rows_left"] + s["attrs"]["rows_right"]
        for s in device + host)
    assert j["rows_small"] == sum(
        min(s["attrs"]["rows_left"], s["attrs"]["rows_right"])
        for s in device + host)
    assert 0 < 2 * j["rows_small"] <= j["rows_device"] + j["rows_host"]
    matched = device + [s for s in spans if s["name"] == "join:probe"
                        and s["attrs"]["step"] == "match"]
    assert j["rows_out"] == sum(s["attrs"]["pairs"] for s in matched) > 0
    if mode == "forced-on":
        assert j["pairs_host"] == 0 and j["rows_host"] == 0
        assert all({"capacity", "pairs", "bytes"} <= set(s["attrs"])
                   for s in device)
    if mode == "forced-off":
        assert j["pairs_device"] == 0
        assert "join:device" not in summary["phases"]
    root_span = next(s for s in spans if s["name"] == "query")
    assert root_span["attrs"]["join_max_pair_rows"] == j["max_pair_rows"]
    assert ("join_pairs_device" in root_span["attrs"]) == \
        (j["pairs_device"] > 0)


def test_device_join_span_is_a_leaf_and_holds_its_fetch(runs):
    assert "join:device" in tracing.LEAF_SPANS
    _, summary, spans, _ = runs["q3", "forced-on"]
    device = [s for s in spans if s["name"] == "join:device"]
    ids = {s["span_id"] for s in device}
    # no leaf nests in it (its fetch is its own); the launch of its one
    # program does, and is no leaf
    inside = [s for s in spans if s.get("parent_id") in ids]
    assert ids and {s["name"] for s in inside} == {"dispatch:launch"}
    assert len(inside) >= len(device)
    assert {s["attrs"]["program"] for s in inside} == {"kernels.join_fused"}
    p = summary["phases"]["join:device"]
    assert p["count"] == len(device) and p["bytes"] > 0
    assert p["rows"] == summary["joins"]["rows_device"]
    assert summary["covered_us"] >= p["wall_us"] > 0


def test_device_join_span_counts_as_covered():
    tracing.reset_for_tests()
    rec = tracing.SpanRecorder("d" * 32)
    with tracing.attach(tracing.SpanContext(rec, rec.root_id)):
        with tracing.span("join:device", attrs={"rows": 7}):
            time.sleep(0.01)
        tracing.tally_max("join_max_pair_rows", 7)
        tracing.tally_max("join_max_pair_rows", 3)
    rec.finish()
    (s,) = tracing.finished()
    assert s["covered_us"] == s["phases"]["join:device"]["wall_us"] >= 9_000
    assert s["joins"] == {"pairs_device": 0, "pairs_host": 0,
                          "rows_device": 0, "rows_host": 0,
                          "max_pair_rows": 7, "rows_small": 0,
                          "rows_out": 0}
    assert s["plan"] == {"repeated_scans": 0}
    tracing.reset_for_tests()


def test_a_host_only_query_tallies_host_pairs_only(root, monkeypatch):
    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    monkeypatch.delenv("DAFT_TPU_DEVICE_JOIN", raising=False)
    tracing.reset_for_tests()
    build = importlib.import_module("chipbench.queries.q3").build
    build(lambda t: daft_tpu.read_parquet(
        f"{root}/{t}/*.parquet")).to_pydict()
    (s,) = tracing.finished()
    j = s["joins"]
    assert j["pairs_host"] > 0 and j["rows_host"] >= j["max_pair_rows"] > 0
    assert j["pairs_device"] == 0 and j["rows_device"] == 0
    assert "join:device" not in s["phases"]
    tracing.reset_for_tests()


# ------------------------------------------------- the gate, by its prices

#: links read on the chip: PR 23's range (its fastest and slowest; the
#: download rate was not recorded, the upload's stands in) and PR 38's
#: machine (rtt ms, up MB/s, down MB/s); and a link that costs nothing
LINKS = {"pr23-fast": (1.1, 2900, 2900), "pr23-slow": (2.7, 2400, 2400),
         "pr38": (1.96, 3326, 1018), "free": (0.0, 1e9, 1e9)}
#: the largest bucket pairs of Q3 and Q10 (left x right rows): at SF1
#: (ledger, PR 37's cell) and at SF10 (my chip runs, PR 38)
PAIRS = {"sf1-q3": (9_400, 200_000), "sf1-q10": (3_700, 94_000),
         "sf10-q3": (94_000, 2_000_000), "sf10-q10": (36_000, 938_000)}


@pytest.mark.parametrize("link", LINKS)
@pytest.mark.parametrize("pair", PAIRS)
def test_the_gate_keeps_a_bucket_pair_on_the_host(monkeypatch, pair, link):
    """Read on the chip (PR 38): the fused join matches 2.0 M rows/s of
    device time and the host 10 M rows/s, so no link makes the device the
    cheaper side, at SF1's pair sizes or at SF10's. The parent priced the
    SF10 pair at 83.9 ms (host) against 95.5 ms (device) on PR 38's link
    and would have flipped at a download rate over 1.6 GB/s."""
    rtt, up, down = LINKS[link]
    monkeypatch.setenv("DAFT_TPU_LINK_RTT_MS", str(rtt))
    monkeypatch.setenv("DAFT_TPU_LINK_UP_MBPS", str(up))
    monkeypatch.setenv("DAFT_TPU_LINK_DOWN_MBPS", str(down))
    monkeypatch.delenv("DAFT_TPU_DEVICE_FORCE", raising=False)
    cm.reset_for_tests()
    try:
        n_l, n_r = PAIRS[pair]
        assert not cm.join_wins(n_l, n_r, 9 * (n_l + n_r),
                                16 * max(n_l, n_r))
        assert cm.decision_counts["join"] == {"device": 0, "host": 1}
        monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
        assert cm.join_wins(n_l, n_r, 9 * (n_l + n_r), 16 * max(n_l, n_r))
    finally:
        cm.reset_for_tests()


def test_the_parents_prices_sat_on_the_break_even(monkeypatch):
    """What ISSUE 38 found and the chip confirmed: under the rates that
    were never read (25e6 / 40e6 rows/s) the SF10 pair's decision hangs
    on the probed download rate."""
    monkeypatch.setattr(cm, "HOST_JOIN_ROWS_PER_S", 25.0e6)
    monkeypatch.setattr(cm, "DEV_JOIN_ROWS_PER_S", 40.0e6)
    monkeypatch.setenv("DAFT_TPU_LINK_RTT_MS", "1.96")
    monkeypatch.setenv("DAFT_TPU_LINK_UP_MBPS", "3326")
    n_l, n_r = 92_320, 2_005_193          # my chip run, PR 38
    try:
        for down, device in ((1018, False), (1500, False), (1700, True),
                             (2900, True)):
            monkeypatch.setenv("DAFT_TPU_LINK_DOWN_MBPS", str(down))
            cm.reset_for_tests()
            assert cm.join_wins(n_l, n_r, 9 * (n_l + n_r),
                                16 * n_r) is device
    finally:
        cm.reset_for_tests()
