"""Subprocess harness for the trace-capturing PR-12 assertions.

jax.profiler's CPU XPlane collector in the pinned jaxlib is not
crash-safe for the REST of a long-lived process: after any trace cycle,
the 3-node cluster fixtures with monitoring collection enabled segfault
(reproduced minimally: one start/stop + NodeServer cluster + collection
thread). Production treats this the same way — the prebuilt breach
capture traces only on TPU (monitoring/slo._default_breach_profile_ms,
DIVERGENCES "Compiled-program introspection") — so the tier-1 process
itself must stay trace-free. Every assertion that actually starts a
trace therefore runs HERE, in a disposable subprocess driven by
tests/test_flight_recorder.py: the engine, waves, watcher, and REST
surface are all real; only the process boundary is test scaffolding.

Prints one line `HARNESS_JSON:{...}` with every observed result; the
parent test asserts on it.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import tempfile
import time

# `python tests/_profiler_harness.py` puts tests/ (not the repo root)
# on sys.path
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta"]


def _run_wave(svc, bodies):
    from concurrent.futures import wait

    entries = [svc.classify("idx", b, {}) for b in bodies]
    assert all(e is not None for e in entries)
    futs = [svc.submit(e) for e in entries]
    wait(futs, timeout=120)
    return [f.result(timeout=1) for f in futs]


def _engine_part(out: dict) -> None:
    from elasticsearch_tpu.engine.engine import Engine

    data = tempfile.mkdtemp()
    e = Engine(os.path.join(data, "data"))
    idx = e.create_index("idx", {"properties": {
        "title": {"type": "text"}, "tag": {"type": "keyword"}}})
    for i in range(60):
        idx.index_doc(str(i), {
            "title": f"{WORDS[i % 7]} {WORDS[(i + 2) % 7]} common",
            "tag": WORDS[i % 3]})
    idx.refresh()
    e.settings.update({"persistent": {
        "serving.flight_recorder.size": 8}})
    svc = e.serving
    for _ in range(3):
        _run_wave(svc, [
            {"query": {"match": {"title": "alpha"}}, "size": 5},
            {"query": {"term": {"tag": "beta"}}, "size": 4},
        ])
    svc.drain()

    # ---- bounded capture ------------------------------------------------
    prof = e.profiler
    out["capture"] = prof.capture(duration_s=0.05, reason="unit")
    out["trace_dir"] = prof.trace_dir()

    # ---- single process-wide trace slot (incl. cross-engine) -----------
    out["start"] = prof.start(duration_s=5.0)
    out["second_start"] = prof.start()
    other = Engine()
    try:
        out["other_engine_start"] = other.profiler.start()
    finally:
        other.close()
    # closing the OTHER engine must not have stopped OUR trace
    out["active_after_other_close"] = prof.status()["active"]
    out["stop"] = prof.stop()

    # ---- watchdog force-stop --------------------------------------------
    prof.start(duration_s=0.2)
    deadline = time.time() + 10.0
    while time.time() < deadline and prof.status()["active"]:
        time.sleep(0.05)
    st = prof.status()
    out["watchdog_active"] = st["active"]
    out["watchdog_capture"] = st["last_capture"]

    # ---- retention prune ------------------------------------------------
    e.settings.update({"persistent": {"xpack.profiling.retention": "1h"}})
    stale = os.path.join(prof.trace_dir(), "capture-1000")
    os.makedirs(stale, exist_ok=True)
    out["pruned"] = prof.prune()
    out["stale_exists"] = os.path.exists(stale)
    out["retained_captures"] = prof.list_captures()
    out["profiler_status"] = {
        k: prof.status()[k]
        for k in ("captures_total", "active", "max_duration_s")}

    # ---- breach-triggered capture (acceptance) --------------------------
    e.settings.update({"persistent": {"slo.custom": json.dumps([
        {"id": "injected-breach",
         "path": "counters.es.device.host_transitions.fetch",
         "max": 0.0},
    ])}})
    out["breached"] = e.slo.evaluate()["breached"]
    from elasticsearch_tpu import xpack

    xpack.watcher_ensure_executor(e)
    prebuilt = e.meta.extras["watches"]["slo-compliance"]
    out["prebuilt_has_capture"] = (
        "capture" in prebuilt["actions"]["capture_diagnostics"])
    e.watcher.put("breach-capture", {
        "trigger": {"schedule": {"interval": "1h"}},
        "input": {"slo": {}},
        "condition": {"compare": {
            "ctx.payload.breached_count": {"gt": 0}}},
        "actions": {"cap": {"capture": {
            "flight_recorder": True, "profile_ms": 100}}},
    })
    res = e.watcher.execute("breach-capture")
    out["watch_record"] = res["watch_record"]
    fl = e.search_multi(".flight-recorder-*", query={"match_all": {}},
                        size=100)
    out["flight_docs"] = [h["_source"] for h in fl["hits"]["hits"]]
    out["last_capture"] = e.profiler.last_capture
    hist = e.search_multi(
        ".watcher-history-8-*",
        query={"term": {"watch_id": "breach-capture"}}, size=5)
    out["history_actions"] = (
        hist["hits"]["hits"][0]["_source"]["actions"])
    svc.stop()
    e.close()


def _host_event_names(capture: dict) -> list[str]:
    """Names of the events on the capture's host planes."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        capture["dir"], "**", "*.xplane.pb"), recursive=True))[-1]
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return sorted(names)


async def _capture_of_a_search(client, out: dict) -> None:
    """One search under `POST /_profiler/start`'s capture: the host planes'
    event names (PR 26)."""
    await client.put("/p", json={"mappings": {"properties": {
        "body": {"type": "text"}}}})
    lines = []
    for i in range(300):  # past the 256 docs an incremental refresh takes
        lines.append(json.dumps({"index": {"_id": str(i)}}))
        lines.append(json.dumps(
            {"body": f"{WORDS[i % 7]} {WORDS[(i + 3) % 7]} common"}))
    r = await client.post("/p/_bulk", data="\n".join(lines) + "\n",
                          headers={"Content-Type": "application/x-ndjson"})
    assert r.status == 200, await r.text()
    await client.post("/p/_refresh")

    async def search(text):  # one plan shape, and never the request cache's
        r = await client.post("/p/_search",
                              json={"query": {"match": {"body": text}}})
        assert r.status == 200, await r.text()

    await search("alpha gamma")
    r = await client.post("/_profiler/start", json={})
    assert r.status == 200, await r.text()
    await search("beta delta")
    stopped = await (await client.post("/_profiler/stop")).json()
    out["capture_host_events"] = _host_event_names(stopped)
    from elasticsearch_tpu import telemetry

    out["annotating_after_stop"] = telemetry._annotation is not None


async def _rest_part(out: dict) -> None:
    from aiohttp.test_utils import TestClient, TestServer

    from elasticsearch_tpu.rest.app import make_app

    client = TestClient(TestServer(make_app()))
    await client.start_server()
    try:
        r = await client.post("/_profiler/start", json={"duration": "2s"})
        out["rest_start"] = {"status": r.status, **(await r.json())}
        r2 = await client.post("/_profiler/start", json={})
        out["rest_second_start_status"] = r2.status
        r3 = await client.post("/_profiler/stop")
        out["rest_stop"] = {"status": r3.status, **(await r3.json())}
        r4 = await client.post("/_profiler/stop")
        out["rest_stop_again_status"] = r4.status
        out["rest_status"] = await (await client.get("/_profiler")).json()
        await _capture_of_a_search(client, out)
    finally:
        engine = client.server.app["engine"]
        if engine._serving is not None:
            engine._serving.stop()
        await client.close()


def main() -> int:
    out: dict = {}
    _engine_part(out)
    asyncio.run(_rest_part(out))
    sys.stdout.write("HARNESS_JSON:" + json.dumps(out, default=str) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
