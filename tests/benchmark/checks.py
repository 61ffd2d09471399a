"""What must hold of any `BENCHMARK.json` and the files it names, as functions
of a root directory. The tests run them on the repo's own file and on a
scratch copy of it that has grown by a configuration, a mix, a cell and a
metric, so that what a later PR may add as files is what the tests accept.
No check knows a cell's, a configuration's or a mix's name or size, nor where
in a list an entry stands."""

import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run as harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
END_TO_END = {"search_p50_ms", "search_p95_ms", "search_qps", "setup_s"}
# a declaration pinned by its name (PR 27): `check_declared` holds it
DISPATCH_BUFFERS = {
    "name": "engine.dispatch_buffers", "unit": "count", "better": "lower",
    "source": "program_counter", "layer": "host planning, dispatch and fetch",
    "moves": "search_p50_ms"}


def bench_json(root: str = REPO) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cells_of(root: str = REPO) -> list[str]:
    return [w["name"] for w in bench_json(root)["workloads"]]


def check_contract_shape(root: str) -> None:
    b = bench_json(root)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "benchmark/run.py"]
    assert set(b["paths"]) == {"benchmark", "tests/benchmark"}
    assert 1 <= b["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in b["end_to_end"])
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] == "host_clock"
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in b[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    configs = {c["name"] for c in b["configs"]}
    for w in b["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(cells) // 2)
    for m in b["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= cells
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}


def check_cell(root: str, cell: str) -> None:
    """The cell resolves to its files, its configuration's kind to a module
    that provides every seam, and the files say what any cell's must."""
    spec = harness.resolve(root, cell)
    config, traffic = spec["config"], spec["traffic"]
    assert config["chips"] == spec["cell"]["chips"] and config["chips"] in (1, 4)
    assert 1 <= config["number_of_shards"]
    assert 0 < config["documents"] < config["source_documents"]
    for key, why in config["reduced"].items():
        assert key in config and isinstance(why, str) and why
    for key in config.get("settings", {}):
        assert config["settings_why"][key]
    assert traffic["name"] == spec["cell"]["traffic"]
    assert traffic["clients"] >= 1 and traffic["warmup_max_passes"] >= 1
    assert traffic["pool"] >= traffic["check_sample"] >= 1
    assert traffic["rate"] is None or traffic["rate"] > 0
    assert {m["name"] for m in spec["end_to_end"]} == END_TO_END
    assert NAME.match(config.get("kind", harness.DEFAULT_KIND))
    for seam in harness.KIND_SEAMS:
        assert callable(getattr(spec["kind"], seam, None)), seam
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        assert callable(harness.layer_reader(spec["bench_dir"], m["name"]))


def check_configuration(root: str, entry: dict) -> None:
    """A `configs` entry and its file state the same cut, and the file its
    guarantees."""
    assert os.path.dirname(entry["file"]) == "benchmark/configs"
    with open(os.path.join(root, entry["file"])) as f:
        c = json.load(f)
    assert c["name"] == entry["name"]
    assert c["source"] == entry["source"] and len(entry["source"]) <= 200
    assert set(entry["reduced"]) == set(c["reduced"])
    assert c["assumed"] and c["guarantees"] and c["limits"]
    assert c["documents"] < c["source_documents"]


def check_declared(root: str, want: dict, cells: list[str]) -> None:
    """The per-layer metric `want["name"]` is declared with `want`'s keys,
    wherever it stands in the list, and each of `cells` reports it."""
    entry = next(m for m in bench_json(root)["per_layer"]
                 if m["name"] == want["name"])
    assert {k: entry[k] for k in want} == want
    for cell in cells:
        reported = [m["name"] for m in harness.resolve(root, cell)["per_layer"]]
        assert want["name"] in reported, cell
    assert callable(harness.layer_reader(
        os.path.join(root, "benchmark"), want["name"]))


def check_all(root: str) -> None:
    """Every check tier-1 applies to the repo's own file, over `root`."""
    check_contract_shape(root)
    b = bench_json(root)
    for cell in b["workloads"]:
        check_cell(root, cell["name"])
    for entry in b["configs"]:
        check_configuration(root, entry)
