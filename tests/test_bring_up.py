"""PR 22 bring-up repairs: the compile cache can be placed from outside,
device peaks are keyed by the kind the chip reports, the device memory
readout covers every local device, and nothing quietly stands in for the
chip (bench preflight, c5 probe environment)."""

import importlib.util
import os
import sys
import types

import numpy as np
import pytest

from elasticsearch_tpu.monitoring import costmodel
from elasticsearch_tpu.utils import jax_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them: the test
    process must not grow a persistent cache."""
    calls = {}
    monkeypatch.setattr(jax_env.jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    monkeypatch.setattr(jax_env, "_cache_done", False)
    return calls


def test_compile_cache_dir_from_environment_is_left_to_jax(
        monkeypatch, config_updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax_env.enable_compile_cache()
    assert "jax_compilation_cache_dir" not in config_updates


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
        monkeypatch, config_updates, tmp_path):
    # fixed, inside the checkout: no pid, time or HOME in it
    assert jax_env.COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax_env, "COMPILE_CACHE_DIR",
                        str(tmp_path / ".jax_cache"))
    jax_env.enable_compile_cache()
    assert config_updates["jax_compilation_cache_dir"] == str(
        tmp_path / ".jax_cache")
    assert os.path.isdir(tmp_path / ".jax_cache")


def test_compile_cache_unwritable_directory_is_an_error(
        monkeypatch, config_updates, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    monkeypatch.setattr(jax_env, "COMPILE_CACHE_DIR", str(blocker / "cache"))
    with pytest.raises(OSError):
        jax_env.enable_compile_cache()
    assert "jax_compilation_cache_dir" not in config_updates


def _fake_device(monkeypatch, platform, kind):
    import jax

    monkeypatch.setattr(costmodel, "_peaks_cache", None)
    monkeypatch.setattr(
        jax, "devices",
        lambda *a: [types.SimpleNamespace(platform=platform,
                                          device_kind=kind)])


def test_v5e_peaks_by_the_kind_the_chip_reports(monkeypatch):
    # "TPU v5 lite" is what the v5e reported (my chip run, PR 22); the
    # peaks are the Cloud TPU v5e page's
    _fake_device(monkeypatch, "tpu", "TPU v5 lite")
    flops, bw, kind = costmodel.device_peaks()
    assert (flops, bw, costmodel.ici_peak(), kind) == (
        197e12, 819e9, 200e9, "TPU v5 lite")
    monkeypatch.setattr(costmodel, "_peaks_cache", None)


def test_unknown_tpu_kind_is_an_error_not_a_default(monkeypatch):
    _fake_device(monkeypatch, "tpu", "TPU v99 imaginary")
    with pytest.raises(ValueError, match="TPU v99 imaginary"):
        costmodel.device_peaks()
    monkeypatch.setattr(costmodel, "_peaks_cache", None)


def test_device_memory_snapshot_reads_every_local_device():
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from elasticsearch_tpu.monitoring.device import device_memory_snapshot

    devs = jax.devices()[:4]
    x = jax.device_put(np.ones((4, 1024), np.float32),
                       NamedSharding(Mesh(np.array(devs), ("shards",)),
                                     P("shards")))
    snap = device_memory_snapshot()
    rows = {d["id"]: d for d in snap["devices"]}
    assert len(rows) == len(jax.local_devices())
    assert all(rows[d.id]["live_bytes"] >= 4096 for d in devs)
    # reading must not itself create arrays the next reading counts
    assert device_memory_snapshot()["devices"] == snap["devices"]
    del x


def test_solo_search_counts_the_tier_its_program_was_built_with():
    from elasticsearch_tpu.engine import Engine
    from elasticsearch_tpu.telemetry import metrics

    def count():
        return metrics.snapshot()["counters"].get(
            "es.search.topk.xla_topk", 0)

    e = Engine()
    try:
        idx = e.create_index("tier", {"properties": {"b": {"type": "text"}}})
        idx.index_doc("1", {"b": "which tier"})
        idx.refresh()
        before = count()
        idx.search({"match": {"b": "tier"}}, size=1)
        assert count() > before
    finally:
        e.close()


def _index_pack_charge(num_shards):
    """-> (bytes the fielddata breaker holds for the index, pack bytes,
    devices its mesh spans) after indexing the same 64 docs."""
    from elasticsearch_tpu.engine import Engine

    e = Engine()
    try:
        idx = e.create_index(
            "spread", {"properties": {"b": {"type": "text"}}},
            settings={"number_of_shards": num_shards})
        for i in range(64):
            idx.index_doc(str(i), {"b": f"per device budget number {i}"})
        idx.refresh()
        mesh = idx.searcher.mesh
        return (e.breakers.children["fielddata"].used,
                idx.searcher.sp.nbytes(),
                1 if mesh is None else mesh.shape["shards"])
    finally:
        e.close()


@pytest.mark.parametrize("num_shards", [1, 4])
def test_breaker_charges_what_one_device_holds(num_shards):
    # four shards on a four-device mesh put a quarter of the pack on each
    # device; the budget is one device's memory, so a quarter is charged
    used, nbytes, spread = _index_pack_charge(num_shards)
    assert spread == num_shards
    assert used == -(-nbytes // num_shards)


def test_accelerator_without_memory_stats_is_an_error(monkeypatch):
    import jax

    from elasticsearch_tpu.common import breaker

    monkeypatch.setattr(
        jax, "local_devices",
        lambda *a: [types.SimpleNamespace(platform="tpu",
                                          memory_stats=lambda: None)])
    with pytest.raises(TypeError):
        breaker.detect_device_memory_bytes()
    monkeypatch.setattr(
        jax, "local_devices",
        lambda *a: [types.SimpleNamespace(
            platform="tpu", memory_stats=lambda: {"bytes_limit": 123})])
    assert breaker.detect_device_memory_bytes() == 123


def test_cpu_budget_is_the_stated_host_mode_size():
    from elasticsearch_tpu.common import breaker

    assert breaker.detect_device_memory_bytes() == breaker.HOST_MODE_BYTES


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench"] = mod
    spec.loader.exec_module(mod)
    return mod


def test_bench_preflight_off_the_chip_is_an_error(bench):
    with pytest.raises(SystemExit) as e:
        bench.preflight()
    assert "not tpu" in str(e.value)


def test_failed_distributed_initialize_raises(monkeypatch):
    from elasticsearch_tpu.parallel import spmd

    def boom(**_kw):
        raise RuntimeError("coordinator unreachable")

    monkeypatch.setenv("ES_TPU_DIST_COORD", "localhost:1")
    monkeypatch.setattr(spmd, "_dist_initialized", False)
    monkeypatch.setattr(spmd.jax.distributed, "initialize", boom)
    with pytest.raises(RuntimeError, match="coordinator unreachable"):
        spmd.maybe_init_distributed()
