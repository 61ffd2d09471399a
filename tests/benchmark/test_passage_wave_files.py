"""The serving deployment's files (PR 35): the configuration
`msmarco-passage-1shard-serving`, the mix `closed-c64`, the cell
`passage.wave.c64` and the six `wave.*` per-layer metrics. What is true of
these files by their names, beside what `checks.py` asks of any entry."""

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

from benchlib import waves  # noqa: E402
from benchlib.client import Client  # noqa: E402
from benchlib.server import Server  # noqa: E402

CONFIG = "msmarco-passage-1shard-serving"
CELL = "passage.wave.c64"
MIX = "closed-c64"
SAME = ("corpus", "query", "search", "bulk_docs", "documents",
        "source_documents", "reduced", "precision", "limits", "chips",
        "number_of_shards")
NEW_METRICS = {
    "wave.avg_size": {
        "unit": "count", "better": "higher", "source": "program_counter",
        "layer": "serving front end", "moves": "search_qps"},
    "wave.pad_share": {
        "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "device programs", "moves": "search_qps"},
    "wave.coalesce_wait_ms": {
        "unit": "ms", "better": "lower", "source": "program_counter",
        "layer": "serving front end", "moves": "search_p50_ms"},
    "wave.host_ms": {
        "unit": "ms", "better": "lower", "source": "program_span",
        "layer": "host planning, dispatch and fetch", "moves": "search_qps"},
    "wave.fetch_ms": {
        "unit": "ms", "better": "lower", "source": "program_span",
        "layer": "host planning, dispatch and fetch",
        "moves": "search_p50_ms"},
    "wave.programs": {
        "unit": "count", "better": "lower", "source": "program_counter",
        "layer": "device programs", "moves": "setup_s"},
}
# one window's counters as the server ships them: 100 waves of 630 members
# in 1,000 rows, 2.0 s of waiting, and the four stages
BEFORE = {"es.serving.wave.count": 50, "es.serving.wave.members": 300,
          "es.serving.wave.padded_rows": 500, "es.serving.wave.wait_ns": 10 ** 9,
          "es.span.engine.wave_plan.ns": 10 ** 7,
          "es.span.engine.wave_launch.ns": 10 ** 7,
          "es.span.engine.wave_fetch.ns": 10 ** 8,
          "es.span.engine.wave_finish.ns": 10 ** 7,
          "es.jit.cache.wave_program.misses": 12,
          "es.jit.cache.wave_program.hits": 38}
AFTER = {"es.serving.wave.count": 150, "es.serving.wave.members": 930,
         "es.serving.wave.padded_rows": 1500,
         "es.serving.wave.wait_ns": 3 * 10 ** 9,
         "es.span.engine.wave_plan.ns": 10 ** 7 + 30 * 10 ** 6,
         "es.span.engine.wave_launch.ns": 10 ** 7 + 20 * 10 ** 6,
         "es.span.engine.wave_fetch.ns": 10 ** 8 + 250 * 10 ** 6,
         "es.span.engine.wave_finish.ns": 10 ** 7 + 25 * 10 ** 6,
         "es.jit.cache.wave_program.misses": 12,
         "es.jit.cache.wave_program.hits": 138}
EXPECTED = {"wave.avg_size": 6.3, "wave.pad_share": 37.0,
            "wave.coalesce_wait_ms": 2e9 / 630 / 1e6, "wave.host_ms": 0.75,
            "wave.fetch_ms": 2.5, "wave.programs": 12}


def _config(name: str = CONFIG) -> dict:
    return harness.read_json(os.path.join(BENCH, "configs", name + ".json"))


def _read(name: str, run):
    return harness.layer_reader(BENCH, name)(run)


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
    assert set(NEW_METRICS) <= set(names)
    # the cell reports every metric that has no list: all of today's
    assert {m["name"] for m in bench_json()["per_layer"]
            if "workloads" not in m} <= set(names)
    assert len(names) == len(bench_json()["per_layer"])


def test_the_additions_stand_at_the_end_of_their_lists():
    b = bench_json()
    assert b["configs"][-1]["name"] == CONFIG
    assert b["configs"][-1]["reduced"] == ["documents"]
    assert b["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": MIX, "chips": 1,
        "why": b["workloads"][-1]["why"]}
    assert [m["name"] for m in b["per_layer"][-6:]] == list(NEW_METRICS)
    assert [w["name"] for w in b["workloads"][:3]] == [
        "passage.solo.c1", "passage.solo.c8", "passage-4chip.solo.c8"]


def test_the_deployment_is_the_first_one_with_the_front_end_on():
    new, first = _config(), _config("msmarco-passage-1shard")
    for key in SAME:
        assert new[key] == first[key], key
    assert new["source"] != first["source"]      # a source each, and a file each
    assert "serving.enabled" in new["source"]
    assert "rally-tracks msmarco-passage-ranking" in new["source"]
    assert set(new) == set(first)
    assert new["guarantees"][:-1] == first["guarantees"]
    assert "does not depend on the wave" in new["guarantees"][-1]
    assert {k: v for k, v in new["assumed"].items() if k != "clients"} \
        == first["assumed"]
    assert "64" in new["assumed"]["clients"] and "42-core" in new["assumed"]["clients"]


def test_the_settings_are_the_cache_off_the_front_end_on_and_one_stated_default():
    from elasticsearch_tpu.common.settings import default_cluster_settings

    new = _config()
    assert new["settings"] == {"indices.requests.cache.enable": False,
                               "serving.enabled": True,
                               "serving.wave.min_tier": 1}
    assert set(new["settings_why"]) == set(new["settings"])
    assert "not recognized" in new["settings_why"]["serving.wave.min_tier"]
    defaults = {s.key: s.default for s in default_cluster_settings()}
    assert defaults["serving.wave.min_tier"] == 1     # stated at its default
    assert (defaults["serving.max_wave"], defaults["serving.coalesce.max_wait"],
            defaults["serving.queue.max_depth"]) == (256, "2ms", 1000)


def test_the_mix_is_sixty_four_callers_on_a_pool_no_window_replays():
    mix = harness.read_json(os.path.join(BENCH, "traffic", MIX + ".json"))
    assert (mix["clients"], mix["rate"], mix["pool"]) == (64, None, 8192)
    assert mix["pool"] // mix["clients"] == 128
    assert 512 <= mix["check_sample"] <= 1024 and mix["warmup_max_passes"] == 4


@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_a_new_metric_is_declared_and_every_cell_resolves_it(name):
    """No list (two accepted tests hold a scratch cell's metrics against the
    whole of the repo's `per_layer`), so every cell reports it: 0 where no
    wave ended."""
    want = dict(NEW_METRICS[name], name=name)
    checks.check_declared(REPO, want, checks.cells_of())
    entry = next(m for m in bench_json()["per_layer"] if m["name"] == name)
    assert "workloads" not in entry


# -- the six readers -----------------------------------------------------------

@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_a_reader_reads_the_windows_own_waves(name):
    assert _read(name, _run(BEFORE, AFTER)) == pytest.approx(EXPECTED[name])
    # counters that start inside the window (the server's first waves)
    fresh = {k: v for k, v in AFTER.items() if "misses" in k}
    assert _read(name, _run(fresh, AFTER)) is not None


@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_a_reader_finds_nothing_without_the_counters_and_zero_without_a_wave(
        name):
    """No list, so every cell reports the metric: where the front end is off
    (or idle) the program ships its wave counters at 0 and the reader says
    so; only a program from before the counters (the parent of PR 35) gives
    nothing, and the line leaves the metric out."""
    assert _read(name, harness.Run()) is None
    solo = {"es.span.rest.search.count": 4000,
            "es.jit.cache.search_solo.misses": 80}
    assert _read(name, _run(solo, dict(solo, **{
        "es.span.rest.search.count": 8000}))) is None
    # the front end's counters shipped, and no wave in the window
    assert _read(name, _run(AFTER, AFTER)) == (
        12 if name == "wave.programs" else 0.0)
    off = dict.fromkeys(AFTER, 0)
    assert _read(name, _run(off, dict(off, **solo))) == 0


def test_the_shared_arithmetic_of_the_readers():
    run = _run(BEFORE, AFTER)
    assert waves.added(run, waves.WAVES) == 100
    assert waves.added(run, "es.no.such.counter") is None
    assert waves.stage_ms_a_wave(run, "plan") == pytest.approx(0.3)
    assert waves.stage_ms_a_wave(run, "plan", "no_such_stage") is None
    assert waves.mean(run, [waves.MEMBERS], waves.WAVES) == pytest.approx(6.3)
    assert waves.mean(_run(AFTER, AFTER), [waves.MEMBERS], waves.WAVES) == 0.0


# -- the real server, through the new cell ----------------------------------------

class _Kept(Server):
    """The repo's server, its counters read once more before it is stopped."""

    counters: dict = {}
    serving: dict = {}

    def stop(self):
        if self.alive():
            c = Client(self.port)
            _Kept.counters = harness.counters_of(c.node_stats())
            _Kept.serving = c.call("GET", "/_serving/stats")["serving"]
        super().stop()


def test_the_real_server_on_the_cpu_through_the_new_cell(tmp_path):
    """The new configuration and mix as they stand, cut to 3,000 documents, a
    small dictionary, 8 callers and a pool of 96 in a scratch root: the
    settings are accepted, every search rides a wave (no plan shape of
    `search_solo` is compiled), the answers are the reference's, nothing is
    shed, and the six readers find their counters."""
    root = tmp_path / "root"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "elasticsearch_tpu"), root / "elasticsearch_tpu")
    config = _config()
    config.update(documents=3000, bulk_docs=500)
    config["corpus"] = dict(config["corpus"], vocab=2000)
    # off the chip the exact arm serves the waves, one program a batch tier:
    # a floor of 8 under 8 callers leaves one tier, so that two passes of a
    # 96-query pool meet every program the window can ask for
    config["settings"] = dict(config["settings"],
                              **{"serving.wave.min_tier": 8})
    with open(root / "benchmark/configs" / (CONFIG + ".json"), "w") as f:
        json.dump(config, f)
    with open(root / "benchmark/traffic" / (MIX + ".json"), "w") as f:
        json.dump({"name": MIX, "clients": 8, "rate": None, "pool": 96,
                   "warmup_max_passes": 4, "check_sample": 96, "why": "cut"}, f)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    env_before = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    try:
        res = harness.run_cell(CELL, 3500000017, 2.0, False, spec_root=str(root),
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
    assert res["compared"]["total_wrong"]["value"] == 0
    assert res["compared"]["order_wrong"]["value"] == 0
    assert res["compared"]["repeat_diff"]["value"] == 0
    assert res["compared"]["score_gap"]["value"] < 1e-5
    counters = _Kept.counters["counters"]
    sv = _Kept.serving
    assert sv["shed"] == sv["expired"] == sv["errors"] == 0
    assert sv["fallback_solo"] == 0
    # every search of the run rode a wave's term lane, none a plan shape
    assert counters["es.serving.wave.members"] == sv["term_packed"] \
        == counters["es.span.rest.search.count"]
    assert counters["es.jit.cache.search_solo.misses"] == 0
    assert counters["es.serving.wave.padded_rows"] \
        == 8 * counters["es.serving.wave.count"]
    run = _run({}, counters)
    run.before = {"counters": {k: 0 for k in counters}}
    for name in NEW_METRICS:
        assert _read(name, run) is not None, name
    assert _read("wave.avg_size", run) >= 1.0
    assert 0.0 <= _read("wave.pad_share", run) < 100.0
    # a member's own stages: every served search recorded each of them
    for stage in ("engine.queue", "engine.search", "engine.parse",
                  "engine.plan", "engine.dispatch", "engine.fetch",
                  "engine.collect", "rest.respond"):
        assert counters[f"es.span.{stage}.count"] \
            == counters["es.span.rest.search.count"], stage
