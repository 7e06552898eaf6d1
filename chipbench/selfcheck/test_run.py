"""A whole run, walked on the CPU at SF0.01 (``--rehearse``): every cell,
both ``--trace`` values, the lower-precision control, and the timed path
broken underneath. The walk skips only the harness's look for a chip; a
CPU number is never a measurement and the walk never reports success."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import run

ROOT = run.ROOT
CELLS = [c["name"] for c in run.load_json(ROOT, "BENCHMARK.json")["workloads"]]


def _walk(cell, trace, seconds=3.0, seed=2147483659):
    args = run.parse(["--workload", cell, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", str(trace), "--rehearse"])
    return run.execute(args)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_walks_and_its_control_is_refused(cell, trace):
    bench = run.load_json(ROOT, "BENCHMARK.json")
    result = _walk(cell, trace)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= len(
        run.load_json(run.HERE, "traffic", run.find_cell(
            bench, cell)["traffic"] + ".json")["queries"])
    # bfloat16 in the engine's place: the comparison must say no
    assert result["control_refused"] is True
    group = "per_layer" if trace else "end_to_end"
    listed = {m["name"] for m in run.metrics_of(bench, group, cell)}
    assert set(result["metrics"]) <= listed
    if not trace:
        assert set(result["metrics"]) == listed
        assert all(m["value"] > 0 for m in result["metrics"].values())
    else:
        # no chip: the device's readers find nothing and stay silent
        assert {"plan_ms", "compiles_in_window", "host_tier_pass_s",
                "dispatches_per_pass"} <= set(result["metrics"])
        assert "device_idle_pct" not in result["metrics"]
        assert result["metrics"]["compiles_in_window"]["value"] == 0


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch):
    import daft_tpu
    real = daft_tpu.DataFrame.to_pydict

    def off_by_a_thousandth(self, *a, **kw):
        out = real(self, *a, **kw)
        for name, col in out.items():
            if col and isinstance(col[0], float):
                out[name] = [col[0] * 1.001] + list(col[1:])
                break
        return out

    monkeypatch.setattr(daft_tpu.DataFrame, "to_pydict", off_by_a_thousandth)
    result = _walk("tpch-sf1.join", 0)
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] > 0


def test_a_query_that_raises_is_a_failed_query(monkeypatch):
    from chipbench.queries import q6

    def broken(get_df):
        raise RuntimeError("no plan")

    # the warm-up refuses to start a window on a mix that raises
    monkeypatch.setattr(q6, "build", broken)
    with pytest.raises(RuntimeError):
        _walk("tpch-sf1.scan-agg-hot", 0)


def test_without_a_chip_nothing_is_printed_as_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        assert not line.startswith("{")


def test_rehearsal_ends_non_zero_and_says_so():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "tpch-sf1.scan-agg-hot", "--seed", "7", "--seconds", "2",
         "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 1
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["ok"] is False
    assert "metrics" not in last
