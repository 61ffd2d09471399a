"""The per-layer metrics of the solo path's completer: `solo.overlap_share`
(the searches whose fetch and what follows ran on the completer thread, in
per cent of the window's searches) and `engine.handoff_ms` (a search's
hand-over, ms over every search). Appended to `per_layer` with no
`workloads` list, so that every cell reports them; what each reads on the
counters of the repo's own server under one client and under eight (in this
process, on the CPU): 0, and above 0; nothing where the server ships no such
counter (a program from before the completer)."""

import asyncio
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import checks  # noqa: E402
from checks import BENCH, REPO, bench_json, harness  # noqa: E402

ENGINE = {"source": "program_counter",
          "layer": "host planning, dispatch and fetch", "moves": "search_qps"}
NEW_METRICS = {
    "solo.overlap_share": dict(ENGINE, unit="%", better="higher"),
    "engine.handoff_ms": dict(ENGINE, unit="ms", better="lower",
                              source="program_span"),
}
# the counter each reads, beside `es.span.rest.search.count`
READS = {"solo.overlap_share": "es.search.solo.handed_off",
         "engine.handoff_ms": "es.span.engine.handoff.ns"}
WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta"]
# the cells accepted when the metrics were added: each of them reports every
# one, whether the metric has no `workloads` list or a list that names them
ACCEPTED = ("passage.solo.c1", "passage.solo.c8", "passage-4chip.solo.c8",
            "passage.wave.c64", "passage.solo.fresh")


def _read(name: str, run):
    return harness.layer_reader(BENCH, name)(run)


def _run(before: dict, after: dict):
    run = harness.Run()
    run.before, run.after = {"counters": before}, {"counters": after}
    return run


@pytest.fixture(scope="module")
def windows():
    """{"c1" | "c8": (counters before, counters after)}: searches through
    the REST layer from one client, then from eight at once."""
    from aiohttp.test_utils import TestClient, TestServer

    from elasticsearch_tpu import telemetry
    from elasticsearch_tpu.rest.app import make_app

    def counters():
        return dict(telemetry.metrics.snapshot()["counters"])

    async def go():
        client = TestClient(TestServer(make_app()))
        await client.start_server()
        out = {}
        try:
            await client.put("/_cluster/settings", json={"persistent": {
                "indices.requests.cache.enable": False}})
            await client.put("/h", json={"mappings": {"properties": {
                "body": {"type": "text"}}}})
            lines = []
            for i in range(300):
                lines.append(json.dumps({"index": {"_id": str(i)}}))
                lines.append(json.dumps(
                    {"body": f"{WORDS[i % 7]} {WORDS[(i + 3) % 7]} common"}))
            await client.post("/h/_bulk", data="\n".join(lines) + "\n",
                              headers={"Content-Type": "application/x-ndjson"})
            await client.post("/h/_refresh")

            async def search(w):
                r = await client.post("/h/_search", json={
                    "query": {"match": {"body": f"{w} common"}}})
                assert r.status == 200, await r.text()

            await search("alpha")                       # compiles the shape
            before = counters()
            for w in WORDS:
                await search(w)
            out["c1"] = (before, counters())

            async def caller(k):
                for j in range(12):
                    await search(WORDS[(k + j) % 7])

            before = counters()
            await asyncio.gather(*(caller(k) for k in range(8)))
            out["c8"] = (before, counters())
        finally:
            await client.close()
        return out

    return asyncio.run(go())


def test_the_new_metrics_follow_what_was_there():
    """Appended: each stands after the accepted entries (not necessarily
    last: a later change appends after them)."""
    checks.check_all(REPO)
    names = [m["name"] for m in bench_json()["per_layer"]]
    for name in NEW_METRICS:
        assert names.index(name) > names.index("setup.xla_check_s")
    assert names.index("engine.handoff_ms") > names.index("solo.overlap_share")


@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_a_new_metric_is_declared_without_a_list_and_every_cell_has_it(name):
    checks.check_declared(REPO, dict(NEW_METRICS[name], name=name), ACCEPTED)


@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_one_client_reads_0_and_eight_read_above_0(name, windows):
    assert _read(name, _run(*windows["c1"])) == 0.0
    got = _read(name, _run(*windows["c8"]))
    assert got > 0.0
    if name == "solo.overlap_share":
        assert got <= 100.0


def test_the_share_is_the_handed_off_searches_over_the_searches(windows):
    before, after = windows["c8"]

    def added(key):
        return after[key] - before[key]

    assert _read("solo.overlap_share", _run(before, after)) == pytest.approx(
        100.0 * added("es.search.solo.handed_off")
        / added("es.span.rest.search.count"))
    assert added("es.span.engine.handoff.count") == added(
        "es.search.solo.handed_off")


@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_a_reader_finds_nothing_where_the_server_ships_no_such_counter(
        name, windows):
    before, after = windows["c8"]
    assert _read(name, harness.Run()) is None
    old = [{k: v for k, v in c.items() if k != READS[name]}
           for c in (before, after)]
    assert _read(name, _run(*old)) is None
