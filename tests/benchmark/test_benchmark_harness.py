"""The harness as a whole, off the chip: every name in BENCHMARK.json resolves
to a file, files alone add a cell, a run is driven end to end against a stub
of the server (sound, and with the timed path broken underneath), and against
the repo's real server on the CPU."""

import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run as harness  # noqa: E402
from benchlib.reference import Reference  # noqa: E402


def bench_json():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# -- names resolve to files --------------------------------------------------

def test_benchmark_json_keeps_to_the_contract_shape():
    b = bench_json()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "benchmark/run.py"]
    assert set(b["paths"]) == {"benchmark", "tests/benchmark"}
    assert 1 <= b["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in b["end_to_end"])
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] == "host_clock"
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in b[group]]
        assert len(set(names)) == len(names)
        assert all(name.match(n) for n in names)
    e2e = {m["name"] for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= cells
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}


@pytest.mark.parametrize("cell", [w["name"] for w in bench_json()["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    spec = harness.resolve(REPO, cell)
    assert spec["config"]["documents"] == 294912
    assert spec["config"]["settings"] == {"indices.requests.cache.enable": False}
    assert "indices.requests.cache.enable" in spec["config"]["settings_why"]
    assert spec["traffic"]["rate"] is None and spec["traffic"]["pool"] == 96
    assert spec["traffic"]["clients"] == {"closed-c1": 1, "closed-c8": 8}[
        spec["cell"]["traffic"]]
    assert {m["name"] for m in spec["end_to_end"]} == {
        "search_p50_ms", "search_p95_ms", "search_qps", "setup_s"}
    for m in spec["per_layer"]:
        assert callable(harness.layer_reader(spec["bench_dir"], m["name"]))


def test_every_configuration_states_its_cut_and_its_guarantees():
    for entry in bench_json()["configs"]:
        assert os.path.dirname(entry["file"]) == "benchmark/configs"
        with open(os.path.join(REPO, entry["file"])) as f:
            c = json.load(f)
        assert c["source"] == entry["source"] and len(entry["source"]) <= 200
        assert set(entry["reduced"]) == set(c["reduced"])
        assert c["assumed"] and c["guarantees"] and c["limits"]
        assert c["documents"] < c["source_documents"]


def test_unknown_names_are_errors_not_defaults():
    with pytest.raises(harness.BenchError, match="no workload"):
        harness.resolve(REPO, "passage.solo.c64")
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


# -- files alone add a configuration, a mix, a cell and a metric -------------

SCRATCH_METRIC = '''"""answers per client, a scratch metric"""


def read(run):
    return len([r for r in run.requests if r.ok]) / 3
'''


@pytest.fixture()
def scratch_root(tmp_path):
    """A copy of the benchmark with one configuration, one mix, one cell and
    one per-layer metric added as new files and BENCHMARK.json entries; no
    file that was there is edited."""
    root = tmp_path / "root"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "elasticsearch_tpu").mkdir()
    b = bench_json()
    with open(os.path.join(BENCH, "configs", "msmarco-passage-1shard.json")) as f:
        config = json.load(f)
    config.update(name="tiny-4shard", documents=1500, number_of_shards=4,
                  settings={"serving.enabled": True, "serving.max_wave": 256})
    config["corpus"] = dict(config["corpus"], vocab=800)
    (root / "benchmark/configs/tiny-4shard.json").write_text(json.dumps(config))
    (root / "benchmark/traffic/open-r120.json").write_text(json.dumps({
        "name": "open-r120", "clients": 3, "rate": 120.0,
        "pool": 48, "warmup_max_passes": 2, "check_sample": 48, "why": "scratch"}))
    (root / "benchmark/layer_metrics/scratch.answers_per_client.py").write_text(
        SCRATCH_METRIC)
    b["configs"].append({"name": "tiny-4shard", "source": "scratch",
                         "file": "benchmark/configs/tiny-4shard.json",
                         "reduced": ["documents"], "why": "scratch"})
    b["workloads"].append({"name": "tiny.open", "config": "tiny-4shard",
                           "traffic": "open-r120", "chips": 1, "why": "scratch"})
    b["per_layer"].append({"name": "scratch.answers_per_client", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "REST front end", "moves": "search_qps",
                           "workloads": ["tiny.open"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return str(root)


def test_files_alone_add_a_cell_and_the_resolver_finds_them(scratch_root):
    spec = harness.resolve(scratch_root, "tiny.open")
    assert spec["config"]["number_of_shards"] == 4
    assert spec["config"]["settings"]["serving.enabled"] is True
    assert spec["traffic"]["rate"] == 120.0 and spec["traffic"]["clients"] == 3
    names = [m["name"] for m in spec["per_layer"]]
    assert "scratch.answers_per_client" in names
    assert "postings_roofline" not in names          # lists its own cells
    assert "engine.took_mean_ms" in names            # no list: every cell
    old = harness.resolve(scratch_root, "passage.solo.c1")
    assert "scratch.answers_per_client" not in [m["name"] for m in old["per_layer"]]


# -- a stub of the server: the reference behind the REST surface -------------

class StubServer:
    """Answers the requests a run sends from a NumPy reference built out of
    what `_bulk` loaded. `fault` breaks the timed path underneath."""

    def __init__(self, root, data_path, log_path, cache_dir, env=None,
                 fault=None, precision="f64", platform="cpu"):
        self.fault, self.precision, self.platform = fault, precision, platform
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
                                      "device_count": 1, "devices": []},
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


def test_device_idle_and_roofline_read_a_reduced_trace():
    run = harness.Run()
    run.trace = {"busy_s": 0.5, "span_s": 2.0}   # both on the trace's clock
    assert harness.layer_reader(BENCH, "device.idle_pct")(run) == pytest.approx(75.0)
    roof = harness.layer_reader(BENCH, "postings_roofline")
    assert roof(run) is None                          # no request in the span
    run.peak = {"hbm_bytes_per_s": 800.0}
    run.pool = [[0, 1], [1]]
    run._df = np.array([30, 10])
    run.traced = [harness.stats.Request(0, 0, 1, 200), harness.stats.Request(1, 1, 2, 200),
                  harness.stats.Request(1, 1, 2, 500)]
    # (30 + 10 + 10) postings x 8 B / 800 B/s = 0.5 s least, 0.5 s busy
    assert roof(run) == pytest.approx(100.0)


# -- the real server, on the CPU ----------------------------------------------

def test_a_checkout_without_the_program_gives_no_result(tmp_path):
    """Only BENCHMARK.json and the files under `paths`: exit code not 0, and
    nothing on standard output."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "passage.solo.c1",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "elasticsearch_tpu is missing" in p.stderr


def test_the_real_server_on_the_cpu_end_to_end(scratch_root, tmp_path):
    """The harness against the repo's own server at a small size: answers
    agree with the reference, nothing compiles in the window, the request
    cache reads no hit. Off the chip, the same command prints no result."""
    b = json.loads(open(os.path.join(scratch_root, "BENCHMARK.json")).read())
    cfg = os.path.join(scratch_root, "benchmark/configs/tiny-1shard.json")
    with open(os.path.join(BENCH, "configs", "msmarco-passage-1shard.json")) as f:
        config = json.load(f)
    config.update(name="tiny-1shard", documents=2500)
    config["corpus"] = dict(config["corpus"], vocab=1500)
    with open(cfg, "w") as f:
        json.dump(config, f)
    with open(os.path.join(scratch_root, "benchmark/traffic/closed-c2.json"), "w") as f:
        json.dump({"name": "closed-c2", "clients": 2,
                   "rate": None, "pool": 40, "warmup_max_passes": 3,
                   "check_sample": 40, "why": "scratch"}, f)
    b["configs"].append({"name": "tiny-1shard", "source": "scratch",
                         "file": "benchmark/configs/tiny-1shard.json",
                         "reduced": ["documents"], "why": "scratch"})
    b["workloads"].append({"name": "tiny.c2", "config": "tiny-1shard",
                           "traffic": "closed-c2", "chips": 1, "why": "scratch"})
    with open(os.path.join(scratch_root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    work_root = tmp_path / "checkout"
    work_root.mkdir()
    os.symlink(os.path.join(REPO, "elasticsearch_tpu"),
               work_root / "elasticsearch_tpu")
    env_before = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    try:
        with pytest.raises(harness.BenchError, match="not on a TPU"):
            harness.run_cell("tiny.c2", 5, 1.0, False, spec_root=scratch_root,
                             program_root=str(work_root))
        res = harness.run_cell("tiny.c2", 5, 1.5, False, spec_root=scratch_root,
                               program_root=str(work_root), require_chip=False)
    finally:
        if env_before is None:
            del os.environ["JAX_COMPILATION_CACHE_DIR"]
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = env_before
    assert res["correct"] is True and res["failed"] == 0
    assert res["window"]["compiles_in_window"] == 0
    assert res["window"]["request_cache_hits"] == 0
    assert res["window"]["answers_compared"] >= 40
    assert res["compared"]["score_gap"]["value"] < 1e-5
