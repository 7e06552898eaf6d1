"""Loud device failures (PR 23): a device program that fails to lower,
trace or compile fails the QUERY — it never silently becomes a host run —
and the one failure a host run may stand in for (device resource
exhaustion) is counted per site, keeps its first error text, and shows in
``costmodel.ledger_snapshot()`` and ``explain(analyze=True)``."""

import logging

import pytest

import daft_tpu
from daft_tpu import col
from daft_tpu.device import backend, costmodel, fragment, runtime


@pytest.fixture(autouse=True)
def _forced_device(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_DEVICE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    costmodel.reset_for_tests()
    yield
    costmodel.reset_for_tests()


def _agg_query():
    df = daft_tpu.from_pydict({"k": [i % 7 for i in range(5000)],
                               "v": [float(i) for i in range(5000)]})
    return df.where(col("v") >= 0).groupby("k").agg(
        col("v").sum().alias("s")).sort("k")


_SUBMITS = ("submit_fused_agg", "submit_fused_agg_tables")


@pytest.mark.parametrize("exc", [NotImplementedError("Unimplemented "
                                 "primitive in Pallas TPU lowering"),
                                 TypeError("bad trace")],
                         ids=["lowering", "tracing"])
def test_broken_device_program_fails_the_query(monkeypatch, exc):
    def boom(*a, **k):
        raise exc
    for name in _SUBMITS:
        monkeypatch.setattr(fragment, name, boom)
    with pytest.raises(type(exc)):
        _agg_query().to_pydict()
    assert runtime.device_failures() == {}


def test_resource_exhaustion_degrades_counted_and_visible(monkeypatch,
                                                          caplog, capsys):
    want = _agg_query().to_pydict()

    def oom(*a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory while trying "
                           "to allocate 17179869184 bytes.")
    for name in _SUBMITS:
        monkeypatch.setattr(fragment, name, oom)
    with caplog.at_level(logging.WARNING, logger="daft_tpu.device.runtime"):
        assert _agg_query().to_pydict() == want   # ran on the host instead
        _agg_query().to_pydict()
    fails = runtime.device_failures()
    assert fails, "the degraded failure must be counted"
    (site, rec), = fails.items()
    assert site.startswith("executor.fused_agg")
    assert rec["count"] >= 2
    assert "RESOURCE_EXHAUSTED" in rec["first_error"]
    assert costmodel.ledger_snapshot()["device_failures"] == fails
    # logged ONCE per site at WARNING, with the error text
    warned = [r for r in caplog.records if site in r.getMessage()]
    assert len(warned) == 1 and "RESOURCE_EXHAUSTED" in warned[0].getMessage()
    _agg_query().explain(analyze=True)
    out = capsys.readouterr().out
    assert "device failures (ran on the HOST instead)" in out
    assert site in out


def test_device_failed_reraises_everything_but_exhaustion():
    for exc in (NotImplementedError("x"), TypeError("x"), ValueError("x"),
                RuntimeError("INTERNAL: Mosaic failed to compile")):
        with pytest.raises(type(exc)):
            runtime.device_failed("t.site", exc)
    runtime.device_failed("t.site", MemoryError())
    runtime.device_failed("t.site", RuntimeError("RESOURCE_EXHAUSTED: hbm"))
    assert runtime.device_failures()["t.site"]["count"] == 2


def test_backend_probe_failure_is_logged_with_its_text(monkeypatch, caplog):
    import jax
    backend.reset_for_tests()
    monkeypatch.setattr(jax, "default_backend", lambda: (_ for _ in ()).throw(
        RuntimeError("TPU initialization failed: chip is held")))
    try:
        with caplog.at_level(logging.WARNING,
                             logger="daft_tpu.device.backend"):
            assert backend.backend_name() is None
            assert backend.backend_name() is None
        assert "chip is held" in backend.probe_error()
        hits = [r for r in caplog.records if "chip is held" in r.getMessage()]
        assert len(hits) == 1
    finally:
        monkeypatch.undo()
        backend.reset_for_tests()
        assert backend.backend_name() == "cpu"


def test_link_measurement_failure_raises_on_an_accelerator(monkeypatch,
                                                           tmp_path):
    monkeypatch.setenv("DAFT_TPU_LINK_CACHE_PATH", str(tmp_path / "lp.json"))
    monkeypatch.setattr(backend, "backend_name", lambda wait=True: "tpu")

    def broken():
        raise RuntimeError("device_put failed")
    monkeypatch.setattr(costmodel, "_measure", broken)
    costmodel.reset_for_tests()
    with pytest.raises(RuntimeError, match="device_put failed"):
        costmodel.link_profile()


def test_persisted_state_lives_under_the_checkout(monkeypatch):
    import os
    monkeypatch.delenv("DAFT_TPU_LINK_CACHE_PATH", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert costmodel._link_cache_path() == os.path.join(
        repo, ".cache", "link_profile.json")
    assert backend.cache_root() == os.path.join(repo, ".cache")


@pytest.mark.parametrize("env_dir", [None, "/somewhere/else"],
                         ids=["unset", "JAX_COMPILATION_CACHE_DIR"])
def test_one_compile_cache_rule(monkeypatch, env_dir):
    import jax
    set_dirs = []
    real_update = jax.config.update

    def spy(name, value):
        if name == "jax_compilation_cache_dir":
            set_dirs.append(value)
        else:
            real_update(name, value)
    monkeypatch.setattr(jax.config, "update", spy)
    prev = (jax.config.jax_persistent_cache_min_compile_time_secs,
            jax.config.jax_persistent_cache_min_entry_size_bytes)
    try:
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            assert backend.configure_compile_cache("cpu") is None
            assert set_dirs == []        # CPU backend: no persistent cache
            want = __import__("os").path.join(backend.cache_root(), "jax")
            assert backend.configure_compile_cache("tpu") == want
            assert set_dirs == [want]    # fixed path: no pid/time/mkdtemp
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
            assert backend.configure_compile_cache("tpu") == env_dir
            assert set_dirs == []        # the env var rules; code sets none
    finally:
        real_update("jax_persistent_cache_min_compile_time_secs", prev[0])
        real_update("jax_persistent_cache_min_entry_size_bytes", prev[1])
