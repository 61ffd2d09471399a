"""The four-shard, four-chip deployment's files (PR 29): the configuration
`msmarco-passage-4shard-4chip`, its cell `passage-4chip.solo.c8` and the four
per-layer metrics that came with them. What is true of these files by their
names, beside what `checks.py` asks of any entry."""

import json
import os
import shutil
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import checks  # noqa: E402
from checks import BENCH, REPO, bench_json, harness  # noqa: E402

from benchlib.client import Client  # noqa: E402
from benchlib.server import Server  # noqa: E402
from benchlib.stats import Request  # noqa: E402

CONFIG = "msmarco-passage-4shard-4chip"
CELL = "passage-4chip.solo.c8"
SHAPES = ("corpus", "query", "search", "bulk_docs", "precision", "limits")
NEW_METRICS = {
    "device.collective_ms": {
        "unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "kernels", "moves": "search_p50_ms"},
    "device.busy_skew": {
        "unit": "x", "better": "lower", "source": "device_trace",
        "layer": "device", "moves": "search_qps"},
    "setup.wal_syncs_per_bulk": {
        "unit": "count", "better": "lower", "source": "program_counter",
        "layer": "write path", "moves": "setup_s"},
    "setup.refresh_shards_at_once": {
        "unit": "x", "better": "higher", "source": "program_counter",
        "layer": "write path", "moves": "setup_s"},
}


def _config(name: str = CONFIG) -> dict:
    return harness.read_json(os.path.join(BENCH, "configs", name + ".json"))


def _read(name: str, run):
    return harness.layer_reader(BENCH, name)(run)


# -- the files -----------------------------------------------------------------

def test_the_new_files_pass_every_check():
    checks.check_all(REPO)
    spec = harness.resolve(REPO, CELL)
    assert spec["cell"]["chips"] == 4 and spec["cell"]["traffic"] == "closed-c8"
    assert spec["config"]["name"] == CONFIG
    # every metric without a list, and the four that came with the cell
    names = [m["name"] for m in spec["per_layer"]]
    assert set(NEW_METRICS) <= set(names)
    assert {m["name"] for m in bench_json()["per_layer"]
            if "workloads" not in m} <= set(names)


def test_the_shapes_are_the_one_shard_files():
    new, first = _config(), _config("msmarco-passage-1shard")
    for key in SHAPES:
        assert new[key] == first[key], key
    assert new["documents"] == 4 * 294912 == 4 * first["documents"]
    assert new["source_documents"] == first["source_documents"] == 8841823
    assert new["chips"] == new["number_of_shards"] == 4
    assert set(new["reduced"]) == {"documents", "number_of_shards"}
    assert new["source"] != first["source"]      # a source each, and a file each


def test_the_guarantees_are_the_first_files_and_index_wide_statistics():
    new, first = _config(), _config("msmarco-passage-1shard")
    assert new["guarantees"][:len(first["guarantees"])] == first["guarantees"]
    more = new["guarantees"][len(first["guarantees"]):]
    assert len(more) == 3
    assert "whole index" in more[0] and "dfs_query_then_fetch" in more[0]
    assert "shard asc" in more[1] and "four chips" in more[2]
    assert "shard-local" not in json.dumps(new)


def test_the_settings_are_the_cache_off_and_one_builder_a_shard():
    new = _config()
    assert new["settings"] == {"indices.requests.cache.enable": False,
                               "indexing.refresh.shard_builders": 4}
    assert set(new["settings_why"]) == set(new["settings"])
    assert "not recognized" in new["settings_why"]["indexing.refresh.shard_builders"]


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_metric_is_declared_and_every_cell_reports_it(name):
    """No list: `test_benchmark_harness.py` holds a scratch cell's metrics
    against the whole of the repo's `per_layer`, so a metric of the repo's own
    file is one that every cell can report. The two that read the capture's
    device planes say what is true on one plane (no collective: 0 ms; a skew
    of 1) where ISSUE.md had them silent."""
    want = dict(NEW_METRICS[name], name=name)
    checks.check_declared(REPO, want, checks.cells_of())
    entry = next(m for m in bench_json()["per_layer"] if m["name"] == name)
    assert "workloads" not in entry


# -- the four readers ------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_reader_finds_nothing_on_an_empty_run(name):
    assert _read(name, harness.Run()) is None
    run = harness.Run()
    run.before = {"counters": {"es.span.rest.search.count": 3}}   # the parent's
    run.trace = {"per_device_busy_s": {}}                         # no plane
    assert _read(name, run) is None


def test_wal_syncs_per_bulk_is_syncs_over_requests_at_window_start():
    run = harness.Run()
    run.before = {"counters": {"es.wal.syncs": 236.0, "es.bulk.requests": 236.0}}
    assert _read("setup.wal_syncs_per_bulk", run) == 1.0
    run.before["counters"]["es.wal.syncs"] = 236.0 * 5000
    assert _read("setup.wal_syncs_per_bulk", run) == 5000.0
    run.before["counters"]["es.bulk.requests"] = 0
    assert _read("setup.wal_syncs_per_bulk", run) is None


def test_refresh_shards_at_once_is_the_shards_time_over_the_stages_wall():
    run = harness.Run()
    run.before = {"counters": {"es.refresh.shard_build.ns": 3.6e10,
                               "es.refresh.build_wall.ns": 1.0e10}}
    assert _read("setup.refresh_shards_at_once", run) == pytest.approx(3.6)
    run.before["counters"]["es.refresh.build_wall.ns"] = 0
    assert _read("setup.refresh_shards_at_once", run) is None


def test_busy_skew_is_the_busiest_plane_over_the_mean():
    run = harness.Run()
    run.trace = {"per_device_busy_s": {"a": 1.0, "b": 1.0, "c": 1.0, "d": 3.0}}
    assert _read("device.busy_skew", run) == pytest.approx(2.0)
    run.trace = {"per_device_busy_s": {"a": 2.0, "b": 2.0, "c": 2.0, "d": 2.0}}
    assert _read("device.busy_skew", run) == pytest.approx(1.0)
    run.trace = {"per_device_busy_s": {"/device:TPU:0": 0.9}}     # one chip
    assert _read("device.busy_skew", run) == 1.0


def _plane(name, events):
    """A device plane as `trace.device_events` walks it."""
    return SimpleNamespace(name=name, lines=[SimpleNamespace(
        name="XLA Ops", events=[
            SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in events])])


def test_collective_ms_is_the_first_planes_collectives_a_request():
    run = harness.Run()
    run.traced = [Request(0, 0.0, 0.1, 200), Request(1, 0.1, 0.2, 200),
                  Request(1, 0.1, 0.2, 500)]
    ops = [("%all-gather.3 = f32[4,10] all-gather(...)", 0, 40_000),
           ("%fusion.1 = f32[8] fusion(...)", 50_000, 900_000),
           ("%all-reduce.1 = s32[] all-reduce(...)", 1_000_000, 20_000),
           ("%collective-permute.7 = f32[2] collective-permute(...)",
            1_100_000, 40_000),
           ("%gather.2 = f32[8] gather(...)", 1_200_000, 500_000)]
    run._capture_profile = SimpleNamespace(planes=[
        _plane("/device:TPU:0", ops),
        _plane("/device:TPU:1", [(n, s, d * 10) for n, s, d in ops]),
        _plane("/host:CPU", [("all-gather on the host", 0, 10 ** 9)])])
    # 100 us of collectives on the first plane over two answered requests
    assert _read("device.collective_ms", run) == pytest.approx(0.05)
    run._capture_profile = SimpleNamespace(planes=[
        _plane("/device:TPU:0", ops[1:2] + ops[4:])])
    assert _read("device.collective_ms", run) == 0.0      # one chip: none ran
    run._capture_profile = SimpleNamespace(planes=[
        _plane("/host:CPU", [("all-gather on the host", 0, 10 ** 9)])])
    assert _read("device.collective_ms", run) is None     # no device plane


# -- the real server, through the new configuration -------------------------------

class _Kept(Server):
    """The repo's server, its counters read once more before it is stopped."""

    counters: dict = {}

    def stop(self):
        if self.alive():
            _Kept.counters = harness.counters_of(Client(self.port).node_stats())
        super().stop()


def test_the_real_server_on_the_cpu_through_the_new_configuration(
        tmp_path, capfd):
    """The new configuration as it stands, cut to 4 x 150 documents and a
    small dictionary in a scratch root, on four host devices (tests/conftest.py
    forces eight; the server, a child, inherits XLA_FLAGS): its settings are
    accepted, the pack lies on four devices, the answers are the reference's
    with index-wide statistics, and the write path's counters read one sync a
    `_bulk` request and four shards built."""
    root = tmp_path / "root"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "elasticsearch_tpu"), root / "elasticsearch_tpu")
    config = _config()
    config.update(documents=600, bulk_docs=100)
    config["corpus"] = dict(config["corpus"], vocab=900)
    with open(root / "benchmark/configs" / (CONFIG + ".json"), "w") as f:
        json.dump(config, f)
    with open(root / "benchmark/traffic/closed-c8.json", "w") as f:
        json.dump({"name": "closed-c8", "clients": 8, "rate": None, "pool": 16,
                   "warmup_max_passes": 3, "check_sample": 16, "why": "cut"}, f)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    env_before = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    try:
        res = harness.run_cell(CELL, 2900000051, 1.5, False, spec_root=str(root),
                               program_root=str(root), require_chip=False,
                               server_factory=_Kept)
    finally:
        if env_before is None:
            del os.environ["JAX_COMPILATION_CACHE_DIR"]
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = env_before
    spread = [ln for ln in capfd.readouterr().err.splitlines()
              if "spread: bytes held per device" in ln]
    held = json.loads(spread[0].split("per device ", 1)[1])
    assert sum(h * 8 >= sum(held) for h in held) == 4
    assert res["correct"] is True and res["failed"] == 0
    assert res["window"]["compiles_in_window"] == 0
    assert res["window"]["setup"]["docs"] == 600
    assert res["compared"]["total_wrong"]["value"] == 0
    assert res["compared"]["order_wrong"]["value"] == 0
    assert res["compared"]["score_gap"]["value"] < 1e-5
    run = harness.Run()
    run.before = _Kept.counters
    counters = run.before["counters"]
    assert counters["es.bulk.requests"] == 6
    assert _read("setup.wal_syncs_per_bulk", run) == 1.0
    assert _read("setup.refresh_shards_at_once", run) > 0
    assert counters["es.refresh.shard_build.ns"] > 0
