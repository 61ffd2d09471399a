"""The harness as a whole, off the chip: every name in BENCHMARK.json resolves
to a file, files alone add a cell (to a scratch copy and to the repo's own
file alike: `checks.py` holds what must be true of any entry, and one test
pins today's deployment by the names of its files), a run is driven end to end
against a stub of the server (sound, with the timed path broken underneath,
with a pack that is not spread over its chips), and against the repo's real
server on the CPU, on one shard and on four."""

import json
import os
import shutil
import subprocess
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import checks  # noqa: E402
from checks import BENCH, REPO, bench_json, harness  # noqa: E402

from benchlib.reference import Reference  # noqa: E402


# -- names resolve to files --------------------------------------------------

def test_benchmark_json_keeps_to_the_contract_shape():
    checks.check_contract_shape(REPO)


@pytest.mark.parametrize("cell", checks.cells_of())
def test_every_cell_resolves_to_its_files(cell):
    checks.check_cell(REPO, cell)


PINNED = {
    # today's deployment, by the names of its files: what is true of these
    # and need not be of the next configuration or mix
    "configs/msmarco-passage-1shard": {
        "chips": 1, "number_of_shards": 1, "documents": 294912,
        "source_documents": 8841823, "bulk_docs": 5000,
        "settings": {"indices.requests.cache.enable": False},
        "search": {"field": "body", "size": 10},
        "limits": {"total_wrong": 0, "rank_gap": 2e-4, "score_gap": 2e-4,
                   "order_wrong": 0, "repeat_diff": 0}},
    "traffic/closed-c1": {"clients": 1, "rate": None, "pool": 96,
                          "warmup_max_passes": 4, "check_sample": 72},
    "traffic/closed-c8": {"clients": 8, "rate": None, "pool": 96,
                          "warmup_max_passes": 4, "check_sample": 72},
}


@pytest.mark.parametrize("file", sorted(PINNED))
def test_the_first_deployments_files_say_what_they_said(file):
    got = harness.read_json(os.path.join(BENCH, file + ".json"))
    assert got["name"] == os.path.basename(file)
    assert {k: got[k] for k in PINNED[file]} == PINNED[file]
    if file.startswith("configs/"):
        assert "indices.requests.cache.enable" in got["settings_why"]
        assert set(got["reduced"]) == {"documents"}


@pytest.mark.parametrize("cell, config, traffic", [
    ("passage.solo.c1", "msmarco-passage-1shard", "closed-c1"),
    ("passage.solo.c8", "msmarco-passage-1shard", "closed-c8")])
def test_the_first_two_cells_stay_what_they_were(cell, config, traffic):
    spec = harness.resolve(REPO, cell)
    assert spec["cell"]["chips"] == 1
    assert (spec["cell"]["config"], spec["cell"]["traffic"]) == (config, traffic)
    assert spec["config"]["name"] == config and spec["traffic"]["name"] == traffic
    bounds = {m["name"]: m["bound"] for m in spec["end_to_end"]}
    assert bounds == {"search_p50_ms": 0.14, "search_p95_ms": 0.19,
                      "search_qps": 0.19, "setup_s": 0.25}


# metric: (PR 25's bound, the cap). A bound is what the rule of README.md,
# "Bounds", gives from measured spreads: a whole number of hundredths, never
# below the first nor above 0.25, and above the second only where PERF.md
# says in so many words that the benchmark cannot resolve that metric
RULE = {"search_p50_ms": (0.06, 0.10), "search_p95_ms": (0.12, 0.15),
        "search_qps": (0.07, 0.12), "setup_s": (0.25, 0.25)}


@pytest.mark.parametrize("metric", sorted(RULE))
def test_every_bound_has_the_form_the_rule_gives(metric):
    bound = {m["name"]: m["bound"] for m in bench_json()["end_to_end"]}[metric]
    floor, cap = RULE[metric]
    assert floor <= bound <= 0.25
    assert bound * 100 == pytest.approx(round(bound * 100), abs=1e-9)
    if bound > cap:
        with open(os.path.join(REPO, "PERF.md")) as f:
            assert f"cannot resolve `{metric}`" in f.read()


def test_every_configuration_states_its_cut_and_its_guarantees():
    for entry in bench_json()["configs"]:
        checks.check_configuration(REPO, entry)


def test_unknown_names_are_errors_not_defaults():
    with pytest.raises(harness.BenchError, match="no workload"):
        harness.resolve(REPO, "no such cell")
    with pytest.raises(harness.BenchError, match="no reader"):
        harness.layer_reader(BENCH, "no.such.metric")
    peaks = harness.read_json(os.path.join(BENCH, "peaks.json"))
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    node = {"device": {"memory": {"backend": "tpu", "device_kind": "TPU v9",
                                  "device_count": 1}}}
    with pytest.raises(harness.BenchError, match="not in peaks.json"):
        harness.device_of(node, peaks, 1, True)
    node["device"]["memory"].update(backend="cpu", device_kind="cpu")
    with pytest.raises(harness.BenchError, match="not on a TPU"):
        harness.device_of(node, peaks, 1, True)
    node["device"]["memory"].update(backend="tpu", device_kind="TPU v5 lite",
                                    device_count=4)
    with pytest.raises(harness.BenchError, match="sees 4 chips"):
        harness.device_of(node, peaks, 1, True)
    node["device"]["memory"].update(device_count=1)
    with pytest.raises(harness.BenchError, match="sees 1 chips"):
        harness.device_of(node, peaks, 4, True)


# -- files alone add a configuration, a mix, a cell and a metric -------------

SCRATCH_METRIC = '''"""answers per client, a scratch metric"""


def read(run):
    return len([r for r in run.requests if r.ok]) / 3
'''

BUSY_SKEW_METRIC = '''"""Device: the busiest plane's busy time over the mean of the planes', a
scratch metric of a cell on several chips. Nothing on one plane."""


def read(run):
    busy = list((run.trace or {}).get("per_device_busy_s", {}).values())
    if len(busy) < 2 or not sum(busy):
        return None
    return max(busy) * len(busy) / sum(busy)
'''


def first_config() -> dict:
    return harness.read_json(
        os.path.join(BENCH, "configs", "msmarco-passage-1shard.json"))


def add_files(root, config=None, traffic=None, cell=None, metric=None,
              reader=None) -> None:
    """Grow the benchmark under `root` the way a later PR may: new files and
    new entries of BENCHMARK.json, no file that is there edited."""
    root = str(root)
    b = bench_json(root)
    if config is not None:
        file = f"benchmark/configs/{config['name']}.json"
        assert not os.path.exists(os.path.join(root, file))
        with open(os.path.join(root, file), "w") as f:
            json.dump(config, f)
        b["configs"].append({"name": config["name"], "source": config["source"],
                             "file": file, "reduced": sorted(config["reduced"]),
                             "why": "scratch"})
    if traffic is not None:
        file = os.path.join(root, f"benchmark/traffic/{traffic['name']}.json")
        assert not os.path.exists(file)
        with open(file, "w") as f:
            json.dump(dict(traffic, why="scratch"), f)
    if cell is not None:
        b["workloads"].append(dict(cell, why="scratch"))
    if metric is not None:
        file = os.path.join(root, f"benchmark/layer_metrics/{metric['name']}.py")
        assert not os.path.exists(file)
        with open(file, "w") as f:
            f.write(reader)
        b["per_layer"].append(metric)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)


@pytest.fixture()
def scratch_root(tmp_path):
    """A copy of the benchmark with one configuration, one mix, one cell and
    one per-layer metric added as new files and BENCHMARK.json entries; no
    file that was there is edited."""
    root = tmp_path / "root"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "elasticsearch_tpu").mkdir()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    config = first_config()
    config.update(name="tiny-4shard", source="scratch", documents=1500,
                  number_of_shards=4,
                  settings={"serving.enabled": True, "serving.max_wave": 256},
                  settings_why={"serving.enabled": "scratch",
                                "serving.max_wave": "scratch"})
    config["corpus"] = dict(config["corpus"], vocab=800)
    add_files(
        root, config,
        {"name": "open-r120", "clients": 3, "rate": 120.0, "pool": 48,
         "warmup_max_passes": 2, "check_sample": 48},
        {"name": "tiny.open", "config": "tiny-4shard", "traffic": "open-r120",
         "chips": 1},
        {"name": "scratch.answers_per_client", "unit": "count",
         "better": "higher", "source": "program_counter",
         "layer": "REST front end", "moves": "search_qps",
         "workloads": ["tiny.open"]}, SCRATCH_METRIC)
    return str(root)


def test_files_alone_add_a_cell_and_the_resolver_finds_them(scratch_root):
    spec = harness.resolve(scratch_root, "tiny.open")
    assert spec["config"]["number_of_shards"] == 4
    assert spec["config"]["settings"]["serving.enabled"] is True
    assert spec["traffic"]["rate"] == 120.0 and spec["traffic"]["clients"] == 3
    names = [m["name"] for m in spec["per_layer"]]
    assert "scratch.answers_per_client" in names
    # no list: every cell, so the new one has the stage split from its first run
    assert {"engine.took_mean_ms", "postings_roofline", "engine.dispatch_ms",
            "device.topk_ms"} <= set(names)
    assert names[:-1] == [m["name"] for m in bench_json()["per_layer"]]
    old = harness.resolve(scratch_root, "passage.solo.c1")
    assert "scratch.answers_per_client" not in [m["name"] for m in old["per_layer"]]


FOUR_CHIPS = {
    "name": "scratch-4shard-4chip",
    "source": "scratch: the first deployment's source, one index of four "
              "primary shards on the four chips of one host",
    "chips": 4, "number_of_shards": 4, "documents": 1179648,
    "reduced": {"documents": "8,841,823 -> 1,179,648: four shards of 294,912",
                "number_of_shards": "30 -> 4: the shards one host holds"},
    "assumed": {"why": "scratch"},
    "guarantees": ["IDF and avgdl are the whole index's, as "
                   "search_type=dfs_query_then_fetch gives them"],
}


@pytest.fixture()
def grown_root(scratch_root):
    """`scratch_root` grown once more, by what the next `model_config` PR
    brings: a four-shard configuration on four chips at its real size, a mix,
    a cell on four chips, and a per-layer metric appended after the last."""
    add_files(
        scratch_root, dict(first_config(), **FOUR_CHIPS),
        {"name": "closed-c3", "clients": 3, "rate": None, "pool": 24,
         "warmup_max_passes": 3, "check_sample": 12},
        {"name": "scratch-4chip.solo.c3", "config": "scratch-4shard-4chip",
         "traffic": "closed-c3", "chips": 4},
        {"name": "scratch.busy_skew", "unit": "x", "better": "lower",
         "source": "device_trace", "layer": "device", "moves": "search_qps",
         "workloads": ["scratch-4chip.solo.c3"]}, BUSY_SKEW_METRIC)
    return scratch_root


def test_the_repos_own_file_grown_by_files_alone_passes_every_check(grown_root):
    """What tier-1 asks of the repo's BENCHMARK.json, asked of the same file
    after a later PR's additions: the checks range over entries, none of them
    knows today's sizes, names or the position of an entry."""
    b = bench_json(grown_root)
    assert len(b["workloads"]) == len(bench_json()["workloads"]) + 2
    assert b["per_layer"][-1]["name"] == "scratch.busy_skew"
    checks.check_all(grown_root)
    checks.check_declared(grown_root, checks.DISPATCH_BUFFERS,
                          checks.cells_of(grown_root))
    spec = harness.resolve(grown_root, "scratch-4chip.solo.c3")
    assert spec["config"]["documents"] == 1179648 and spec["cell"]["chips"] == 4
    # the cell reports what every cell does, and its own metric besides
    names = [m["name"] for m in spec["per_layer"]]
    assert names == [m["name"] for m in bench_json()["per_layer"]] + [
        "scratch.busy_skew"]
    run = harness.Run()
    read = harness.layer_reader(spec["bench_dir"], "scratch.busy_skew")
    assert read(run) is None
    run.trace = {"per_device_busy_s": {"a": 1.0, "b": 1.0, "c": 1.0, "d": 3.0}}
    assert read(run) == pytest.approx(2.0)


def test_a_cell_whose_chips_are_not_its_configurations_is_refused(grown_root):
    add_files(grown_root, cell={"name": "scratch-4chip.on-one",
                                "config": "scratch-4shard-4chip",
                                "traffic": "closed-c3", "chips": 1})
    with pytest.raises(harness.BenchError, match="asks for 1 chips"):
        harness.resolve(grown_root, "scratch-4chip.on-one")


# -- a stub of the server: the reference behind the REST surface -------------

class StubServer:
    """Answers the requests a run sends from a NumPy reference built out of
    what `_bulk` loaded. `fault` breaks the timed path underneath."""

    def __init__(self, root, data_path, log_path, cache_dir, env=None,
                 fault=None, precision="f64", platform="cpu", devices=()):
        self.fault, self.precision, self.platform = fault, precision, platform
        self.devices = [{"id": i, "live_bytes": b} for i, b in enumerate(devices)]
        self.docs, self.settings, self.ref = {}, {}, None
        self.shards, self.searches, self.port, self.httpd = 1, 0, None, None
        self.log_path = log_path
        self.lock = threading.Lock()

    def start(self):
        from benchlib.client import Client

        stub = self

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def _body(self):
                n = int(self.headers.get("Content-Length") or 0)
                return self.rfile.read(n) if n else b""

            def _send(self, obj, status=200):
                raw = json.dumps(obj).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(raw)))
                self.end_headers()
                self.wfile.write(raw)

            def do_GET(self):
                self._body()
                self._send(stub.get(self.path))

            def do_PUT(self):
                self._send(stub.post(self.path, self._body()))

            do_POST = do_PUT

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.port = self.httpd.server_address[1]
        threading.Thread(target=self.httpd.serve_forever, daemon=True).start()
        return Client(self.port)

    def alive(self):
        return self.httpd is not None

    def stop(self):
        if self.httpd is not None:
            self.httpd.shutdown()
            self.httpd.server_close()
            self.httpd = None

    def log_tail(self, lines=40):
        return ""

    def get(self, path):
        if path == "/_nodes/stats":
            return {"nodes": {"n": {
                "device": {"memory": {"backend": self.platform,
                                      "device_kind": self.platform,
                                      "device_count": len(self.devices) or 1,
                                      "devices": self.devices},
                           "jit": {"compiles": 3, "compile_time_in_millis": 1500}},
                "indices": {"request_cache": {"hit_count": 0}},
                "breakers": {"fielddata": {"estimated_size_in_bytes": 1,
                                           "limit_size_in_bytes": 2}},
                "metrics": {"counters": {}}}}}
        return {}

    def post(self, path, body):
        if path == "/_cluster/settings":
            self.settings.update(json.loads(body)["persistent"])
            return {"acknowledged": True}
        if path.endswith("/_bulk"):
            lines = body.decode().splitlines()
            items = []
            for meta, doc in zip(lines[0::2], lines[1::2]):
                i = int(json.loads(meta)["index"]["_id"])
                self.docs[i] = [int(w[1:]) for w in json.loads(doc)["body"].split()]
                items.append({"index": {"_id": str(i), "status": 201}})
            return {"errors": False, "items": items}
        if path.endswith("/_refresh"):
            n = len(self.docs)
            assert sorted(self.docs) == list(range(n))
            lens = np.array([len(self.docs[i]) for i in range(n)])
            tok = np.concatenate([self.docs[i] for i in range(n)])
            self.ref = Reference(lens, tok, self.shards, precision=self.precision)
            return {"_shards": {"total": 1, "successful": 1, "failed": 0}}
        if path.endswith("/_search"):
            return self.search(json.loads(body))
        if path.startswith("/c1"):
            self.shards = json.loads(body)["settings"]["number_of_shards"]
            return {"acknowledged": True}
        raise AssertionError(path)

    def search(self, body):
        terms = [int(w[1:]) for w in body["query"]["match"]["body"].split()]
        with self.lock:
            self.ref.prepare({t for t in terms if t not in self.ref._postings})
        ids, scores, total = self.ref.top(terms, body["size"])
        relation = "eq"
        self.searches += 1
        if self.fault == "answer_altered" and len(ids) > 2:
            ids[-1] = (ids[0] + 1 + self.ref.n // 2) % self.ref.n
        elif self.fault == "total_is_a_lower_bound" and total > 10:
            relation, total = "gte", 10
        elif self.fault == "hit_dropped" and len(ids) > 2:
            ids, scores = ids[:-1], scores[:-1]
        elif self.fault == "stale_answer" and self.searches % 7 == 0 and len(ids) > 1:
            ids = ids[1:] + ids[:1]
        return {"took": 1, "timed_out": False,
                "hits": {"total": {"value": total, "relation": relation},
                         "max_score": scores[0] if scores else None,
                         "hits": [{"_index": "c1", "_id": str(d), "_score": s}
                                  for d, s in zip(ids, scores)]}}


def _drive(scratch_root, workload="tiny.open", traced=False, **stub_kw):
    def factory(*a, **kw):
        return StubServer(*a, **kw, **stub_kw)

    return harness.run_cell(workload, seed=2**31 + 77, seconds=1.0,
                            traced=traced, spec_root=scratch_root,
                            program_root=scratch_root, require_chip=False,
                            server_factory=factory)


# -- a cell on four chips: the pack has to lie on them -----------------------

def _memory(*held, key="live_bytes"):
    return {"device": {"memory": {"devices": [{"id": i, key: b}
                                              for i, b in enumerate(held)]}}}


@pytest.mark.parametrize("held, require_chip, spread", [
    ((250, 250, 250, 250), True, True),
    ((400, 100, 100, 200), True, True),       # an eighth each is enough
    ((1000, 0, 0, 0), True, False),           # no mesh: the pack on one device
    ((400, 400, 400, 90), True, False),       # one chip all but empty
    ((250, 250, 250), True, False),           # fewer devices than chips
    ((200, 200, 200, 200, 200), True, False),  # on the chips, exactly as many
    ((300, 260, 250, 250, 0, 0, 0, 0), False, True),   # eight host devices
    ((1000, 10, 10, 10, 0, 0, 0, 0), False, False),
])
def test_the_spread_of_a_pack_over_its_chips(held, require_chip, spread):
    if spread:
        assert harness.pack_spread(_memory(*held), 4, require_chip) == list(held)
        # the allocator's own count where the backend has one
        assert harness.pack_spread(_memory(*held, key="bytes_in_use"), 4,
                                   require_chip) == list(held)
    else:
        with pytest.raises(harness.BenchError,
                           match="not spread over 4 devices: bytes held per "
                                 r"device \[" + str(held[0])):
            harness.pack_spread(_memory(*held), 4, require_chip)


@pytest.fixture()
def four_chip_root(scratch_root):
    """A cell on four chips small enough to drive: the scratch root's four
    shards, on four chips."""
    config = harness.read_json(os.path.join(
        scratch_root, "benchmark/configs/tiny-4shard.json"))
    config.update(name="tiny-4shard-4chip", chips=4, settings={})
    add_files(scratch_root, config,
              {"name": "closed-c2", "clients": 2, "rate": None, "pool": 12,
               "warmup_max_passes": 3, "check_sample": 12},
              {"name": "tiny-4chip.c2", "config": "tiny-4shard-4chip",
               "traffic": "closed-c2", "chips": 4})
    return scratch_root


def test_a_pack_on_one_device_of_four_gives_no_result(four_chip_root, capsys):
    with pytest.raises(harness.BenchError, match="not spread over 4 devices"):
        _drive(four_chip_root, "tiny-4chip.c2", devices=(4096, 0, 0, 0))
    # through the command: exit code 1, the spread named, nothing on stdout
    def factory(*a, **kw):
        return StubServer(*a, **kw, devices=(4096, 0, 0, 0))

    rc = harness.main(["--workload", "tiny-4chip.c2", "--seed", "3",
                       "--seconds", "1", "--trace", "0"],
                      spec_root=four_chip_root, program_root=four_chip_root,
                      require_chip=False, server_factory=factory)
    out = capsys.readouterr()
    assert rc == 1 and out.out == ""
    assert "not spread over 4 devices: bytes held per device [4096, 0, 0, 0]" \
        in out.err


def test_a_pack_spread_evenly_goes_on_and_reports_each_devices_peak(
        four_chip_root, capsys):
    res = _drive(four_chip_root, "tiny-4chip.c2",
                 devices=(1024, 1024, 1024, 1024))
    assert res["correct"] is True and res["failed"] == 0
    assert "spread: bytes held per device [1024, 1024, 1024, 1024]" in \
        capsys.readouterr().err
    assert res["device"]["count"] == 4
    assert res["device"]["memory_peak_bytes_per_device"] == [0, 0, 0, 0]
    # a cell on one chip is not asked where its pack lies
    assert _drive(four_chip_root, devices=(4096, 0, 0, 0))["correct"] is True


def test_a_run_against_the_sound_stub_is_correct_and_prints_the_contract(scratch_root):
    res = _drive(scratch_root)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 120          # 120 requests a second, offered
    assert list(res)[-1] == "compared"
    assert set(res["metrics"]) == {"search_p50_ms", "search_p95_ms",
                                   "search_qps", "setup_s"}
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert res["device"]["platform"] == "cpu"
    assert res["window"]["compiles_in_window"] == 0
    assert res["window"]["request_cache_hits"] == 0
    assert res["compared"].pop("ids_differ") == {"value": 0, "limit": None}
    assert all(n["value"] <= n["limit"] for n in res["compared"].values())
    assert not os.path.exists(os.path.join(scratch_root, ".bench_work", "tiny.open"))


def test_a_runs_compile_cache_is_its_own_and_goes_with_the_run(
        scratch_root, monkeypatch):
    """No run finds what another compiled: the server is handed a directory
    under the run's own files that is not there before the run, whatever
    `$JAX_COMPILATION_CACHE_DIR` says, and that a good run removes."""
    shared = os.path.join(scratch_root, "shared_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", shared)
    handed = []

    def factory(root, data_path, log_path, cache_dir, env=None):
        handed.append((cache_dir, os.path.exists(cache_dir)))
        os.makedirs(cache_dir)          # what the server's first compile does
        with open(os.path.join(cache_dir, "a_program"), "w") as f:
            f.write("compiled")
        return StubServer(root, data_path, log_path, cache_dir, env)

    for _ in range(2):
        res = harness.run_cell("tiny.open", 7, 1.0, False,
                               spec_root=scratch_root,
                               program_root=scratch_root, require_chip=False,
                               server_factory=factory)
        assert res["correct"] is True
        assert not os.path.exists(handed[-1][0])
    assert [there for _, there in handed] == [False, False]
    work = os.path.join(scratch_root, ".bench_work", "tiny.open")
    assert all(os.path.dirname(d) == work for d, _ in handed)
    assert not os.path.exists(shared)


@pytest.mark.parametrize("fault,number", [
    ("answer_altered", "rank_gap"), ("hit_dropped", "rank_gap"),
    ("total_is_a_lower_bound", "total_wrong"), ("stale_answer", "repeat_diff")])
def test_a_run_with_the_timed_path_broken_underneath_is_not_correct(
        scratch_root, fault, number):
    res = _drive(scratch_root, fault=fault)
    assert res["correct"] is False
    n = res["compared"][number]
    assert n["value"] > n["limit"]


def test_a_run_against_the_bf16_control_in_the_programs_place_is_not_correct(
        scratch_root):
    res = _drive(scratch_root, precision="bf16")
    assert res["correct"] is False
    assert res["compared"]["total_wrong"]["value"] == 0
    assert (res["compared"]["score_gap"]["value"]
            > 10 * res["compared"]["score_gap"]["limit"])


def test_the_look_for_a_chip_refuses_a_cpu_and_prints_no_result(scratch_root, capsys):
    def factory(*a, **kw):
        return StubServer(*a, **kw)

    with pytest.raises(harness.BenchError, match="not on a TPU"):
        harness.run_cell("tiny.open", 1, 1.0, False, spec_root=scratch_root,
                         program_root=scratch_root, server_factory=factory)


def test_per_layer_readers_run_on_the_stub_and_a_reader_with_nothing_to_read_is_left_out(
        scratch_root):
    res = _drive(scratch_root, workload="tiny.open")
    spec = harness.resolve(scratch_root, "tiny.open")
    run = harness.Run()
    run.requests = run.untraced = [
        harness.stats.Request(0, 0.0, 0.010, 200, 7.0),
        harness.stats.Request(1, 0.010, 0.024, 200, 9.0),
        harness.stats.Request(2, 0.02, 0.03, 429)]
    run.before, run.after = {"compiles": 5}, {"compiles": 7}
    run.setup = {"docs": 1000, "load_s": 2.0, "refresh_s": 4.0, "compile_s": 1.5}
    got = {m["name"]: harness.layer_reader(spec["bench_dir"], m["name"])(run)
           for m in spec["per_layer"]}
    assert got["engine.took_mean_ms"] == pytest.approx(8.0)
    assert got["rest.outside_took_ms"] == pytest.approx(4.0)
    assert got["device_programs.compiles_in_window"] == 2
    assert got["setup.load_docs_per_s"] == 500.0
    assert got["setup.refresh_docs_per_s"] == 250.0
    assert got["setup.compile_s"] == 1.5
    assert got["device.idle_pct"] is None            # no trace: nothing, not 0
    assert got["scratch.answers_per_client"] == pytest.approx(2 / 3)
    assert res["correct"]


@pytest.mark.parametrize("planes", [1, 4])
def test_device_idle_and_roofline_read_a_reduced_trace(planes):
    run = harness.Run()
    # busy is the mean over the planes; both on the trace's clock
    run.trace = {"busy_s": 0.5, "span_s": 2.0, "per_device_busy_s": {
        f"/device:TPU:{i}": 0.5 for i in range(planes)}}
    assert harness.layer_reader(BENCH, "device.idle_pct")(run) == pytest.approx(75.0)
    roof = harness.layer_reader(BENCH, "postings_roofline")
    assert roof(run) is None                          # no request in the span
    run.peak = {"hbm_bytes_per_s": 800.0}
    run.pool = [[0, 1], [1]]
    run._df = np.array([30, 10])
    run.traced = [harness.stats.Request(0, 0, 1, 200), harness.stats.Request(1, 1, 2, 200),
                  harness.stats.Request(1, 1, 2, 500)]
    # (30 + 10 + 10) postings x 8 B / 800 B/s = 0.5 s least on one chip, an
    # n-th of it on n that share the work; 0.5 s busy on each
    assert roof(run) == pytest.approx(100.0 / planes)
    del run.trace["per_device_busy_s"]                # a reduction without it: one
    assert roof(run) == pytest.approx(100.0)


# -- the real server, on the CPU ----------------------------------------------

def test_a_checkout_without_the_program_gives_no_result(tmp_path):
    """Only BENCHMARK.json and the files under `paths`: exit code not 0, and
    nothing on standard output."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", checks.cells_of()[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "elasticsearch_tpu is missing" in p.stderr


def _real_server(root, tmp_path, cell, seconds, **kw):
    """`run_cell` against the repo's own server, in a checkout of its own
    (the run's files, its compile cache among them, lie inside it)."""
    work_root = tmp_path / "checkout"
    work_root.mkdir()
    os.symlink(os.path.join(REPO, "elasticsearch_tpu"),
               work_root / "elasticsearch_tpu")
    return harness.run_cell(cell, 5, seconds, False, spec_root=root,
                            program_root=str(work_root), **kw)


def test_the_real_server_on_the_cpu_end_to_end(scratch_root, tmp_path):
    """The harness against the repo's own server at a small size: answers
    agree with the reference, nothing compiles in the window, the request
    cache reads no hit. Off the chip, the same command prints no result."""
    config = first_config()
    config.update(name="tiny-1shard", source="scratch", documents=2500)
    config["corpus"] = dict(config["corpus"], vocab=1500)
    add_files(scratch_root, config,
              {"name": "closed-c2", "clients": 2, "rate": None, "pool": 40,
               "warmup_max_passes": 3, "check_sample": 40},
              {"name": "tiny.c2", "config": "tiny-1shard",
               "traffic": "closed-c2", "chips": 1})
    with pytest.raises(harness.BenchError, match="not on a TPU"):
        _real_server(scratch_root, tmp_path, "tiny.c2", 1.0)
    shutil.rmtree(tmp_path / "checkout")
    res = _real_server(scratch_root, tmp_path, "tiny.c2", 1.5,
                       require_chip=False)
    assert res["correct"] is True and res["failed"] == 0
    assert res["window"]["compiles_in_window"] == 0
    assert res["window"]["request_cache_hits"] == 0
    assert res["window"]["answers_compared"] >= 40
    assert res["compared"]["score_gap"]["value"] < 1e-5


def test_the_real_server_on_the_cpu_four_shards_on_four_devices(
        scratch_root, tmp_path, capfd):
    """The mesh path against the plain reference at a small size: one index of
    four shards on four host devices (tests/conftest.py forces eight, and the
    server, a child, inherits XLA_FLAGS), the source's shapes at 4 x 600
    documents. The pack lies on four devices, every answer agrees with the
    reference, whose ties break by (score, shard, doc) and whose IDF and avgdl
    are the whole index's. What the four-chip configuration rests on."""
    config = first_config()
    config.update(name="tiny-4shard-4dev", source="scratch", chips=4,
                  number_of_shards=4, documents=2400)
    config["corpus"] = dict(config["corpus"], vocab=1500)
    add_files(scratch_root, config,
              {"name": "closed-c2", "clients": 2, "rate": None, "pool": 12,
               "warmup_max_passes": 3, "check_sample": 12},
              {"name": "tiny-4dev.c2", "config": "tiny-4shard-4dev",
               "traffic": "closed-c2", "chips": 4})
    res = _real_server(scratch_root, tmp_path, "tiny-4dev.c2", 1.5,
                       require_chip=False)
    spread = [ln for ln in capfd.readouterr().err.splitlines()
              if "spread: bytes held per device" in ln]
    held = json.loads(spread[0].split("per device ", 1)[1])
    assert len(held) >= 4 and sum(h * 8 >= sum(held) for h in held) == 4
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == len(held) == len(
        res["device"]["memory_peak_bytes_per_device"])
    assert res["window"]["compiles_in_window"] == 0
    assert res["window"]["answers_compared"] >= 12
    assert res["compared"]["total_wrong"]["value"] == 0
    assert res["compared"]["order_wrong"]["value"] == 0
    assert res["compared"]["score_gap"]["value"] < 1e-5
