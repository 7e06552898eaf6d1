"""The harness is driven by data: everything a cell, a query or a metric
needs is a file found by the name ``BENCHMARK.json`` gives. And the data
are the seed's."""

import hashlib
import importlib
import os

import pytest

from chipbench import datagen, run

BENCH = run.load_json(run.ROOT, "BENCHMARK.json")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_a_cell_is_its_files(cell):
    config = run.load_json(run.HERE, "configs", cell["config"] + ".json")
    traffic = run.load_json(run.HERE, "traffic", cell["traffic"] + ".json")
    assert config["name"] == cell["config"] and config["chips"] == cell["chips"]
    assert config["rtol"] <= 1e-4 and config["guarantees"]
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["file"] == f"chipbench/configs/{cell['config']}.json"
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert traffic["cache"] in ("keep", "clear-before-each-query")
    for q in traffic["queries"]:
        query = importlib.import_module(f"chipbench.queries.{q}")
        reference = importlib.import_module(f"chipbench.reference.{q}")
        assert callable(query.build) and callable(reference.answer)
        assert set(query.SCANS) <= set(config["tables"])
        assert reference.COMPARE["kind"] in ("rows", "topk")


@pytest.mark.parametrize("group,package", [("end_to_end", "end_to_end"),
                                           ("per_layer", "layer_metrics")])
def test_a_metric_is_its_reader(group, package):
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {c["name"] for c in BENCH["workloads"]}
    for m in BENCH[group]:
        reader = importlib.import_module(f"chipbench.{package}.{m['name']}")
        assert callable(reader.read) and reader.__doc__
        assert set(m.get("workloads", cells)) <= cells
        if group == "per_layer":
            assert m["moves"] in e2e
    have = {f[:-3] for f in os.listdir(os.path.join(run.HERE, package))
            if f.endswith(".py") and f != "__init__.py"}
    assert {m["name"] for m in BENCH[group]} <= have


def _digest(root):
    h = hashlib.sha256()
    for d, _, names in sorted(os.walk(root)):
        for n in sorted(names):
            if n.endswith(".parquet"):
                h.update(n.encode())
                with open(os.path.join(d, n), "rb") as f:
                    h.update(f.read())
    return h.hexdigest()


def test_data_are_the_seeds_and_the_part_count_is_fixed(tmp_path):
    big = 2**31 + 11
    a = datagen.ensure_dataset(str(tmp_path / "a"), "t", 0.01, 16,
                               datagen.ALL_TABLES, big, 2)
    b = datagen.ensure_dataset(str(tmp_path / "b"), "t", 0.01, 16,
                               datagen.ALL_TABLES, big, 1)
    assert _digest(a) == _digest(b)
    for table in ("lineitem", "orders", "customer", "part", "partsupp",
                  "supplier"):
        assert len(os.listdir(os.path.join(a, table))) == 16
    # another seed is other data, and takes the first seed's place
    c = datagen.ensure_dataset(str(tmp_path / "a"), "t", 0.01, 16,
                               datagen.ALL_TABLES, 5, 1)
    assert _digest(c) != _digest(a) if os.path.exists(a) else True
    assert not os.path.exists(a) and os.path.exists(c)
    only = datagen.ensure_dataset(str(tmp_path / "a"), "u", 0.01, 16,
                                  ("lineitem",), 5, 1)
    assert sorted(os.listdir(only)) == ["_COMPLETE", "lineitem"]
    assert os.path.exists(c)   # another configuration's data stays
