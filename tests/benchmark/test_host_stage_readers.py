"""The per-layer metrics of the host time between and beneath the stages:
eleven readers, each appended to `per_layer` with no `workloads`
list, so that every cell reports it. What each reads on the counters of a
solo run and of a wave run of the repo's own server (in this process, on the
CPU), 0 where its work did not happen, and nothing where the server ships no
such counter (a program from before them)."""

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

ENGINE = {"unit": "ms", "better": "lower", "source": "program_span",
          "layer": "host planning, dispatch and fetch", "moves": "search_qps"}
REST = {"unit": "ms", "better": "lower", "source": "program_span",
        "layer": "REST front end", "moves": "search_p50_ms"}
WAVE = {"unit": "ms", "better": "lower", "source": "program_span",
        "layer": "serving front end", "moves": "search_qps"}
NEW_METRICS = {
    "engine.account_ms": ENGINE, "engine.route_ms": ENGINE,
    "engine.unpack_ms": ENGINE, "engine.unnamed_ms": ENGINE,
    "rest.read_ms": REST, "rest.resume_ms": REST,
    "rest.client_gap_ms": dict(REST, source="program_counter"),
    "wave.queue_ms": WAVE, "wave.handoff_ms": WAVE, "wave.rejoin_ms": WAVE,
    "setup.xla_check_s": {"unit": "s", "better": "lower",
                          "source": "program_counter",
                          "layer": "device programs", "moves": "setup_s"},
}
# the counters each reads, beside `es.span.rest.search.count` and
# `es.serving.wave.count`
READS = {
    "engine.account_ms": ["es.span.engine.account.ns"],
    "engine.route_ms": ["es.span.engine.route.ns"],
    "engine.unpack_ms": ["es.span.engine.unpack.ns"],
    "engine.unnamed_ms": ["es.span.engine.route.ns",
                          "es.span.engine.account.ns",
                          "es.span.engine.unpack.ns"],
    "rest.read_ms": ["es.span.rest.read.ns"],
    "rest.resume_ms": ["es.span.rest.resume.ns"],
    "rest.client_gap_ms": ["es.rest.client_gap.ns"],
    "wave.queue_ms": ["es.span.engine.wave_queue.ns"],
    "wave.handoff_ms": ["es.span.engine.wave_handoff.ns"],
    "wave.rejoin_ms": ["es.span.engine.wave_rejoin.ns"],
    "setup.xla_check_s": ["es.costmodel.xla_check.ns"],
}
# the cells accepted when the eleven were added: each of them reports every
# one, whether the metric has no `workloads` list or a list that names them
ACCEPTED = ("passage.solo.c1", "passage.solo.c8", "passage-4chip.solo.c8",
            "passage.wave.c64", "passage.solo.fresh")
# 0 exactly where the work did not happen: the solo path's own stages in
# the wave run, a wave's hand-offs in the solo run
ZERO_IN = {"solo": {"wave.queue_ms", "wave.handoff_ms", "wave.rejoin_ms"},
           "wave": {"engine.account_ms", "engine.route_ms",
                    "engine.unpack_ms"}}


def _read(name: str, run):
    return harness.layer_reader(BENCH, name)(run)


def _run(before: dict, after: dict):
    run = harness.Run()
    run.before, run.after = {"counters": before}, {"counters": after}
    return run


@pytest.fixture(scope="module")
def windows():
    """{"solo" | "wave": (counters before, counters after)} of six searches
    through the REST layer, the serving front end off and then on."""
    from aiohttp.test_utils import TestClient, TestServer

    from elasticsearch_tpu import telemetry
    from elasticsearch_tpu.rest.app import make_app

    words = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta"]

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
                    {"body": f"{words[i % 7]} {words[(i + 3) % 7]} common"}))
            await client.post("/h/_bulk", data="\n".join(lines) + "\n",
                              headers={"Content-Type": "application/x-ndjson"})
            await client.post("/h/_refresh")

            async def window(kind):
                body = {"query": {"match": {"body": "alpha gamma"}}}
                assert (await client.post("/h/_search", json=body)).status \
                    == 200                       # compiles the shape
                before = counters()
                for w in words[:6]:
                    r = await client.post("/h/_search", json={
                        "query": {"match": {"body": f"{w} common"}}})
                    assert r.status == 200, await r.text()
                out[kind] = (before, counters())

            await window("solo")
            await client.put("/_cluster/settings", json={"persistent": {
                "serving.enabled": True}})
            await window("wave")
        finally:
            engine = client.server.app["engine"]
            if engine._serving is not None:
                engine._serving.stop()
            await client.close()
        return out

    return asyncio.run(go())


def test_the_new_metrics_are_appended_and_every_cell_resolves_them():
    checks.check_all(REPO)
    names = [m["name"] for m in bench_json()["per_layer"]]
    assert names[-len(NEW_METRICS):] == list(NEW_METRICS)
    assert names.index("engine.account_ms") > names.index("solo.pad_share")


@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_a_new_metric_is_declared_without_a_list(name):
    checks.check_declared(REPO, dict(NEW_METRICS[name], name=name), ACCEPTED)


@pytest.mark.parametrize("kind", ["solo", "wave"])
@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_a_reader_reads_a_solo_and_a_wave_window(name, kind, windows):
    before, after = windows[kind]
    got = _read(name, _run(before, after))
    assert got is not None, (name, kind)
    if name in ZERO_IN[kind]:
        assert got == 0.0
    elif name == "setup.xla_check_s":
        assert got >= 0.0
    else:
        assert got > 0.0, (name, kind)


@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_a_reader_finds_nothing_where_the_server_ships_no_such_counter(
        name, windows):
    before, after = windows["solo"]
    assert _read(name, harness.Run()) is None
    old = [{k: v for k, v in c.items() if k not in READS[name]}
           for c in (before, after)]
    assert _read(name, _run(*old)) is None


def test_unnamed_is_engine_search_less_every_stage_under_it(windows):
    before, after = windows["solo"]
    run = _run(before, after)

    def ms(stem):
        return ((after[f"es.span.{stem}.ns"] - before[f"es.span.{stem}.ns"])
                / 6 * 1e-6)

    stages = ("route", "parse", "plan", "account", "dispatch", "fetch",
              "unpack", "collect")
    assert _read("engine.unnamed_ms", run) == pytest.approx(
        ms("engine.search") - sum(ms(f"engine.{s}") for s in stages))
    # the older reader subtracts the five first stages alone, and keeps doing so
    assert _read("engine.unattributed_ms", run) == pytest.approx(
        _read("engine.unnamed_ms", run) + sum(
            ms(f"engine.{s}") for s in ("route", "account", "unpack")))
