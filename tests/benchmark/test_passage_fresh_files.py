"""The dev-queries deployment's files (PR 38): the configuration
`msmarco-passage-1shard-devqueries`, the mix `closed-c8-dev`, the cell
`passage.solo.fresh` and the per-layer metric `solo.pad_share`. What is true
of these files by their names, beside what `checks.py` asks of any entry."""

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import checks  # noqa: E402
from checks import BENCH, REPO, bench_json, harness  # noqa: E402

from benchlib.client import Client  # noqa: E402
from benchlib.server import Server  # noqa: E402

CONFIG = "msmarco-passage-1shard-devqueries"
FIRST = "msmarco-passage-1shard"
CELL = "passage.solo.fresh"
MIX = "closed-c8-dev"
METRIC = "solo.pad_share"
SAME = ("corpus", "query", "search", "bulk_docs", "documents",
        "source_documents", "reduced", "precision", "guarantees", "limits",
        "chips", "number_of_shards")
ROWS, PADDED = "es.search.solo.rows", "es.search.solo.padded_rows"


def _config(name: str = CONFIG) -> dict:
    return harness.read_json(os.path.join(BENCH, "configs", name + ".json"))


def _read(run):
    return harness.layer_reader(BENCH, METRIC)(run)


def _run(before: dict, after: dict):
    run = harness.Run()
    run.before, run.after = {"counters": before}, {"counters": after}
    return run


# -- the files -----------------------------------------------------------------

def test_the_new_files_pass_every_check():
    checks.check_all(REPO)
    spec = harness.resolve(REPO, CELL)
    assert spec["cell"]["chips"] == 1 and spec["cell"]["traffic"] == MIX
    assert spec["config"]["name"] == CONFIG
    names = [m["name"] for m in spec["per_layer"]]
    # the cell reports every metric that has no list, the new one among them
    assert METRIC in names
    assert {m["name"] for m in bench_json()["per_layer"]
            if "workloads" not in m} <= set(names)


def test_the_additions_follow_what_was_there():
    """Appended, and nothing before them moved: each stands after the
    accepted entries of its list (not necessarily last: a later PR appends
    after it)."""
    b = bench_json()
    configs = [c["name"] for c in b["configs"]]
    cells = [w["name"] for w in b["workloads"]]
    metrics = [m["name"] for m in b["per_layer"]]
    assert configs[:4] == [FIRST, "msmarco-passage-4shard-4chip",
                           "msmarco-passage-1shard-serving", CONFIG]
    assert cells[:5] == ["passage.solo.c1", "passage.solo.c8",
                         "passage-4chip.solo.c8", "passage.wave.c64", CELL]
    assert metrics.index(METRIC) > metrics.index("wave.programs")
    entry = b["configs"][configs.index(CONFIG)]
    assert entry["reduced"] == ["documents"]
    assert b["workloads"][cells.index(CELL)] == {
        "name": CELL, "config": CONFIG, "traffic": MIX, "chips": 1,
        "why": b["workloads"][cells.index(CELL)]["why"]}


def test_the_deployment_is_the_first_one_under_the_dev_questions():
    new, first = _config(), _config(FIRST)
    for key in SAME:
        assert new[key] == first[key], key
    assert new["source"] != first["source"]      # a source each, and a file each
    assert "6,980" in new["source"] and "queries.dev.small.tsv" in new["source"]
    assert set(new) == set(first)
    assert {k: v for k, v in new["assumed"].items() if k != "dev_queries"} \
        == first["assumed"]
    assert "6,980" in new["assumed"]["dev_queries"]
    assert "terms_share" in new["assumed"]["dev_queries"]


def test_the_settings_are_the_cache_off_and_the_family_stated_at_its_default():
    from elasticsearch_tpu.common.settings import default_cluster_settings

    new = _config()
    assert new["settings"] == {"indices.requests.cache.enable": False,
                               "search.solo.min_rows_tier": 8}
    assert set(new["settings_why"]) == set(new["settings"])
    assert "not recognized" in new["settings_why"]["search.solo.min_rows_tier"]
    registered = {s.key: s for s in default_cluster_settings()}
    setting = registered["search.solo.min_rows_tier"]
    assert setting.default == 8 and setting.dynamic      # stated at its default


def test_the_mix_is_eight_callers_on_a_pool_no_window_replays():
    mix = harness.read_json(os.path.join(BENCH, "traffic", MIX + ".json"))
    assert (mix["clients"], mix["rate"], mix["pool"]) == (8, None, 6980)
    # each client starts 872 queries on: ~4,200 requests of a 10 s window at
    # ~420 req/s ask 525 a client
    assert mix["pool"] // mix["clients"] == 872
    assert mix["check_sample"] == 1024 and mix["warmup_max_passes"] == 4
    checks.check_cell(REPO, CELL)


def test_the_metric_is_declared_and_every_cell_resolves_it():
    """No list (two accepted tests hold a scratch cell's metrics against the
    whole of the repo's `per_layer`), so every cell reports it: 0 where no
    solo search ran (the wave cell)."""
    want = {"name": METRIC, "unit": "%", "better": "lower",
            "source": "program_counter", "layer": "device programs",
            "moves": "search_qps"}
    checks.check_declared(REPO, want, checks.cells_of())
    entry = next(m for m in bench_json()["per_layer"] if m["name"] == METRIC)
    assert "workloads" not in entry


# -- the reader ----------------------------------------------------------------

@pytest.mark.parametrize("before, after, want", [
    # 400 searches: 2,000 real rows of 3,200 gathered
    ({ROWS: 1000, PADDED: 1600}, {ROWS: 3000, PADDED: 4800}, 37.5),
    # no padding at all
    ({ROWS: 0, PADDED: 0}, {ROWS: 48, PADDED: 48}, 0.0),
    # counters that start inside the window (the server's first searches)
    ({}, {ROWS: 30, PADDED: 40}, 25.0),
])
def test_the_reader_reads_the_windows_own_rows(before, after, want):
    assert _read(_run(before, after)) == pytest.approx(want)


def test_the_reader_finds_nothing_without_the_counters_and_zero_without_a_search():
    """Every cell reports the metric: where no solo search ran (the serving
    cell, whose searches ride waves) the program ships the counters at 0 and
    the reader says 0; only a program from before the family (the parent of
    PR 38) gives nothing, and the line leaves the metric out."""
    assert _read(harness.Run()) is None
    solo = {"es.span.rest.search.count": 4000,
            "es.jit.cache.search_solo.misses": 80}
    assert _read(_run(solo, dict(solo, **{
        "es.span.rest.search.count": 8000}))) is None
    idle = {ROWS: 500, PADDED: 800}
    assert _read(_run(idle, idle)) == 0.0
    off = {ROWS: 0, PADDED: 0}
    assert _read(_run(off, off)) == 0.0


# -- the real server, through the new cell ----------------------------------------

class _Kept(Server):
    """The repo's server, its counters read once more before it is stopped."""

    counters: dict = {}

    def stop(self):
        if self.alive():
            _Kept.counters = harness.counters_of(Client(self.port).node_stats())
        super().stop()


def test_the_real_server_on_the_cpu_through_the_new_cell(tmp_path):
    """The new configuration and mix as they stand, cut to 3,000 documents, a
    small dictionary and a pool of 400 in a scratch root: the setting is
    accepted, the answers are the reference's, no program compiles in the
    window, the plans are the match family's (no more programs than the
    ladders hold for queries of 1-12 words), and the reader finds its
    counters."""
    root = tmp_path / "root"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "elasticsearch_tpu"), root / "elasticsearch_tpu")
    config = _config()
    config.update(documents=3000, bulk_docs=500)
    config["corpus"] = dict(config["corpus"], vocab=2000)
    with open(root / "benchmark/configs" / (CONFIG + ".json"), "w") as f:
        json.dump(config, f)
    with open(root / "benchmark/traffic" / (MIX + ".json"), "w") as f:
        json.dump({"name": MIX, "clients": 8, "rate": None, "pool": 400,
                   "warmup_max_passes": 4, "check_sample": 128, "why": "cut"}, f)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    env_before = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    try:
        res = harness.run_cell(CELL, 3800000017, 2.0, False, spec_root=str(root),
                               program_root=str(root), require_chip=False,
                               server_factory=_Kept)
    finally:
        if env_before is None:
            del os.environ["JAX_COMPILATION_CACHE_DIR"]
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = env_before
    assert res["correct"] is True and res["failed"] == 0, res["compared"]
    assert res["window"]["compiles_in_window"] == 0
    assert res["window"]["request_cache_hits"] == 0
    for name in ("total_wrong", "order_wrong", "repeat_diff"):
        assert res["compared"][name]["value"] == 0, name
    assert res["compared"]["score_gap"]["value"] < 1e-5
    counters = _Kept.counters["counters"]
    # dense tiers 0-8 by rows tiers 8-32, and the one program beyond them
    assert 1 <= counters["es.jit.cache.search_solo.misses"] <= 16
    run = _run({k: 0 for k in counters}, counters)
    assert 0.0 < _read(run) < 100.0
    assert counters[ROWS] > 0
