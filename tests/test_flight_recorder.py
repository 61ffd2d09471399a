"""PR 12: serving-wave flight recorder + breach-triggered capture.

Covers: the bounded per-wave ring (capacity, eviction order, dynamic
resize), segment timings summing to the wave's wall time (contiguous
boundaries by construction), tenant/lane/kernel attribution in-record,
the REST surface (`GET /_serving/flight_recorder`, `_dump` to the
hidden `.flight-recorder-*` index, `POST /_profiler/{start,stop}`),
the duration-bounded ProfilerService (watchdog, single-trace slot,
retention prune), the watcher `capture` action end-to-end (injected SLO
breach -> flight dump doc + non-empty jax.profiler trace), and the
trace_dump --flight renderer.
"""

import asyncio
import io
import json
import os
import sys
from concurrent.futures import wait

import pytest

from elasticsearch_tpu.engine.engine import Engine
from elasticsearch_tpu.serving.service import (
    FLIGHT_INDEX_PREFIX, flight_index_name,
)

WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta"]


@pytest.fixture
def engine(tmp_path):
    e = Engine(str(tmp_path / "data"))
    yield e
    e.close()


@pytest.fixture
def served(engine):
    idx = engine.create_index("idx", {"properties": {
        "title": {"type": "text"}, "tag": {"type": "keyword"}}})
    for i in range(60):
        idx.index_doc(str(i), {
            "title": f"{WORDS[i % 7]} {WORDS[(i + 2) % 7]} common",
            "tag": WORDS[i % 3]})
    idx.refresh()
    svc = engine.serving
    yield engine, idx, svc
    svc.stop()


def _run_wave(svc, bodies, tenants=None):
    entries = [svc.classify("idx", b, {}) for b in bodies]
    assert all(e is not None for e in entries)
    futs = [svc.submit(e, tenant=(tenants[i % len(tenants)]
                                  if tenants else "_anonymous"))
            for i, e in enumerate(entries)]
    wait(futs, timeout=120)
    return [f.result(timeout=1) for f in futs]


def _bodies():
    return [
        {"query": {"match": {"title": "alpha"}}, "size": 5},
        {"query": {"term": {"tag": "beta"}}, "size": 4},
        {"query": {"match": {"title": "common"}}, "size": 10,
         "aggs": {"t": {"terms": {"field": "tag"}}}},
    ]


# ---------------------------------------------------------------------------
# ring semantics
# ---------------------------------------------------------------------------

def test_flight_recorder_records_waves_with_attribution(served):
    engine, _idx, svc = served
    _run_wave(svc, _bodies(), tenants=["tA", "tB"])
    svc.drain()
    snap = svc.flight_recorder()
    assert snap["capacity"] == 256  # the documented default ring bound
    assert snap["retained"] >= 1
    rec = snap["waves"][-1]
    total = sum(w["size"] for w in snap["waves"])
    assert total == len(_bodies())
    # tenant mix + lane breakdown + transitions are in-record (PR 19:
    # each tenant entry carries its request count AND its exact
    # apportioned share of the wave's device segment)
    all_tenants: dict = {}
    for w in snap["waves"]:
        for t, v in w["tenants"].items():
            all_tenants[t] = all_tenants.get(t, 0) + v["requests"]
            assert v["device_ms"] >= 0.0 and 0.0 <= v["share"] <= 1.0
    assert set(all_tenants) == {"tA", "tB"}
    assert rec["indices"] == ["idx"]
    lanes = rec["lanes"]
    assert lanes["generic"] + lanes["term"] + lanes["tiered"] >= 1
    assert rec["host_transitions"]["fetch"] >= 1
    # per-kernel deltas: at least one kernel with utilization attribution
    assert rec["kernels"], rec
    k = next(iter(rec["kernels"].values()))
    assert k["calls"] >= 1 and "mfu" in k and "bw_util" in k


def test_flight_recorder_segments_sum_to_wall_time(served):
    _engine, _idx, svc = served
    for _ in range(3):
        _run_wave(svc, _bodies())
    svc.drain()
    waves = svc.flight_recorder()["waves"]
    assert waves
    for w in waves:
        seg = w["segments_ms"]
        assert set(seg) == {"queue", "plan", "device", "finish"}
        assert all(v >= 0.0 for v in seg.values()), seg
        # contiguous boundaries: the segments ARE a partition of the wall
        assert sum(seg.values()) == pytest.approx(w["wall_ms"], abs=0.01)


def test_flight_recorder_ring_bound_and_eviction_order(served):
    engine, _idx, svc = served
    engine.settings.update({"persistent": {
        "serving.flight_recorder.size": 4}})
    for _ in range(7):
        _run_wave(svc, [{"query": {"match": {"title": "alpha"}},
                         "size": 3}])
    svc.drain()
    snap = svc.flight_recorder()
    assert snap["capacity"] == 4
    assert snap["retained"] <= 4
    assert snap["recorded_total"] >= 7
    ids = [w["wave"] for w in snap["waves"]]
    assert ids == sorted(ids), "ring must retain oldest-first order"
    # the OLDEST waves were evicted, the newest survive
    assert ids[-1] == snap["recorded_total"]
    assert ids[0] == snap["recorded_total"] - len(ids) + 1
    # growing the ring keeps the retained tail
    engine.settings.update({"persistent": {
        "serving.flight_recorder.size": 8}})
    snap2 = svc.flight_recorder()
    assert snap2["capacity"] == 8
    assert [w["wave"] for w in snap2["waves"]] == ids


def test_flight_recorder_dump_writes_hidden_dated_index(served):
    engine, _idx, svc = served
    _run_wave(svc, _bodies())
    svc.drain()
    out = svc.dump_flight_recorder()
    name = flight_index_name()
    assert out["index"] == name and out["docs"] >= 1
    assert out["docs"] <= out["capacity"]
    idx = engine.indices[name]
    assert idx.settings.get("hidden") is True
    res = engine.search_multi(
        FLIGHT_INDEX_PREFIX + "*", query={"match_all": {}}, size=300)
    assert res["hits"]["total"]["value"] == out["docs"]
    src = res["hits"]["hits"][0]["_source"]
    assert "segments_ms" in src and "wall_ms" in src
    # re-dump is idempotent per (node, wave): doc ids are wave sequence
    out2 = svc.dump_flight_recorder()
    res2 = engine.search_multi(
        FLIGHT_INDEX_PREFIX + "*", query={"match_all": {}}, size=300)
    assert res2["hits"]["total"]["value"] == out2["docs"]
    # the CleanerService owns the dated index: a stale one is pruned
    from elasticsearch_tpu.monitoring.service import _index_date

    assert _index_date(FLIGHT_INDEX_PREFIX + "2020.01.01") is not None
    engine.create_index(FLIGHT_INDEX_PREFIX + "2020.01.01",
                        settings={"hidden": True})
    engine.monitoring.prune()
    assert FLIGHT_INDEX_PREFIX + "2020.01.01" not in engine.indices
    assert name in engine.indices


# ---------------------------------------------------------------------------
# profiler service + breach-triggered capture (acceptance)
#
# Every assertion below STARTS a jax.profiler trace, which in the pinned
# jaxlib poisons the rest of a long-lived CPU process (one trace cycle +
# the 3-node cluster fixtures with monitoring collection segfaults —
# reproduced minimally; the prebuilt breach capture traces only on TPU
# for the same reason). The real engine/watcher/REST code therefore runs
# in a disposable subprocess (tests/_profiler_harness.py) and the tests
# assert on its reported results — the process boundary is the only
# scaffolding.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def harness():
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu", ES_TPU_XLA_CHECK="0")
    cmd = [sys.executable, os.path.join(os.path.dirname(__file__),
                                        "_profiler_harness.py")]
    # one retry: the harness spins up a full jax process; under a loaded
    # full-suite run a cold start can exceed its watchdog-ish budget
    last = None
    for _attempt in range(2):
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=420, env=env)
        line = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("HARNESS_JSON:")]
        if proc.returncode == 0 and line:
            return json.loads(line[0][len("HARNESS_JSON:"):])
        last = proc
    raise AssertionError((last.returncode, last.stdout[-4000:],
                          last.stderr[-4000:]))


def test_profiler_capture_bounded_single_slot_and_prune(harness):
    cap = harness["capture"]
    assert cap["stopped"] is True
    assert cap["files"], "trace capture produced no files"
    assert cap["bytes"] > 0
    assert any("xplane" in f or "trace" in f for f in cap["files"])
    # the capture dir lives under the engine's data path by default
    assert cap["dir"].startswith(harness["trace_dir"])
    # single PROCESS-WIDE trace slot: a second start is refused — from
    # this engine and from another engine in the same process — and
    # closing the other engine does not kill the owner's trace
    assert harness["start"]["started"] is True
    assert harness["second_start"]["started"] is False
    assert "active" in harness["second_start"]
    assert harness["other_engine_start"]["started"] is False
    assert harness["active_after_other_close"] is True
    assert harness["stop"]["stopped"] is True
    # retention prune deletes expired capture dirs, keeps fresh ones
    assert "capture-1000" in harness["pruned"]
    assert harness["stale_exists"] is False
    assert harness["retained_captures"]
    st = harness["profiler_status"]
    assert st["captures_total"] >= 2 and st["active"] is False


def test_profiler_watchdog_force_stops_a_forgotten_trace(harness):
    assert harness["watchdog_active"] is False, \
        "watchdog did not stop the trace"
    assert harness["watchdog_capture"]["by_watchdog"] is True


def test_injected_slo_breach_dumps_flight_recorder_and_traces(harness):
    """Acceptance: an injected SLO breach fires a watch whose `capture`
    action dumps the flight recorder (docs <= ring bound, segments
    summing to wall time) AND takes a non-empty profiler trace."""
    assert "injected-breach" in harness["breached"]
    # the prebuilt watch materializes with the capture action
    assert harness["prebuilt_has_capture"] is True
    rec = harness["watch_record"]
    assert rec["condition_met"] is True
    assert rec["actions_executed"] == ["cap"]
    # flight-recorder dump landed as docs, bounded by the ring (size 8)
    docs = harness["flight_docs"]
    assert 1 <= len(docs) <= 8
    for src in docs:
        seg = src["segments_ms"]
        assert sum(seg.values()) == pytest.approx(src["wall_ms"],
                                                  abs=0.01)
    # the profiler trace is non-empty
    cap = harness["last_capture"]
    assert cap is not None and cap["files"] and cap["bytes"] > 0
    assert cap["trigger"] == "watch [breach-capture]"
    # the action detail rode into the watcher history doc
    cap_action = [a for a in harness["history_actions"]
                  if a["id"] == "cap"][0]
    assert cap_action["status"] == "executed"
    assert cap_action["flight_recorder"]["docs"] == len(docs)
    assert cap_action["profile"]["bytes"] > 0


def test_rest_profiler_lifecycle(harness):
    """POST /_profiler/{start,stop}: bounded start, 409 on the occupied
    slot, stop returns the trace inventory (run in the harness process —
    the endpoints start real traces)."""
    assert harness["rest_start"]["status"] == 200
    assert harness["rest_start"]["started"] is True
    assert harness["rest_second_start_status"] == 409
    assert harness["rest_stop"]["status"] == 200
    assert harness["rest_stop"]["stopped"] is True
    assert harness["rest_stop"]["files"]
    assert harness["rest_stop_again_status"] == 409
    assert harness["rest_status"]["captures_total"] >= 1
    assert harness["rest_status"]["max_duration_s"] == 10.0


def test_a_capture_holds_stage_annotations_and_no_python_frame(harness):
    """POST /_profiler/start: the host planes hold one annotation named as
    each leaf stage of a search, PJRT's own events, and no
    `$file.py:line function` frame of the Python tracer (PR 26)."""
    from elasticsearch_tpu.telemetry import ANNOTATED_STAGES, WAVE_STAGES

    names = set(harness["capture_host_events"])
    # the harness's search runs alone: no wave, so none of a wave's stages
    assert ANNOTATED_STAGES - set(WAVE_STAGES) <= names
    assert "PjitFunction(search_solo)" in names
    assert not [n for n in names if n.startswith("$")]


def test_the_stages_stop_annotating_when_the_capture_stops(harness):
    assert harness["annotating_after_stop"] is False


# ---------------------------------------------------------------------------
# REST surface
# ---------------------------------------------------------------------------

async def _client():
    from aiohttp.test_utils import TestClient, TestServer

    from elasticsearch_tpu.rest.app import make_app

    client = TestClient(TestServer(make_app()))
    await client.start_server()
    return client


def test_rest_flight_recorder_and_profiler_surface():
    async def go():
        client = await _client()
        try:
            engine = client.server.app["engine"]
            await client.put("/fr", json={"mappings": {"properties": {
                "title": {"type": "text"}}}})
            for i in range(5):
                await client.put(f"/fr/_doc/{i}?refresh=true",
                                 json={"title": f"alpha w{i}"})
            engine.settings.update({"persistent": {
                "serving.enabled": True}})
            r = await client.post(
                "/fr/_search",
                json={"query": {"match": {"title": "alpha"}}})
            assert r.status == 200
            engine.serving.drain()
            fr = await (await client.get(
                "/_serving/flight_recorder")).json()
            assert fr["capacity"] == 256 and fr["retained"] >= 1
            seg = fr["waves"][-1]["segments_ms"]
            assert sum(seg.values()) == pytest.approx(
                fr["waves"][-1]["wall_ms"], abs=0.01)
            # ?n= limits the returned tail
            one = await (await client.get(
                "/_serving/flight_recorder?n=1")).json()
            assert len(one["waves"]) == 1
            r = await client.post("/_serving/flight_recorder/_dump")
            assert r.status == 200
            dump = await r.json()
            assert dump["docs"] >= 1
            # profiler status endpoint (the start/stop lifecycle — which
            # starts real traces — is exercised in the subprocess
            # harness; see the comment above the `harness` fixture)
            st = await (await client.get("/_profiler")).json()
            assert st["active"] is False
            assert st["enabled"] is True
            assert st["max_duration_s"] == 10.0
            assert (await client.post("/_profiler/stop")).status == 409
        finally:
            engine = client.server.app["engine"]
            if engine._serving is not None:
                engine._serving.stop()
            await client.close()

    asyncio.run(go())


# ---------------------------------------------------------------------------
# trace_dump --flight renderer
# ---------------------------------------------------------------------------

def test_trace_dump_renders_flight_recorder(served, tmp_path, capsys):
    _engine, _idx, svc = served
    _run_wave(svc, _bodies(), tenants=["tA"])
    svc.drain()
    snap = svc.flight_recorder()
    path = tmp_path / "flight.json"
    path.write_text(json.dumps(snap))
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import trace_dump

    rc = trace_dump.main(["--flight", str(path)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "flight recorder:" in out
    assert "wall=" in out and "q/p/d/f=" in out
    # the bar is partitioned by segment glyphs
    assert any(ch in out for ch in ("█", "▒", "░", "▓"))
    # JSON-lines form (a .flight-recorder-* dump) renders too
    jl = tmp_path / "flight.jsonl"
    jl.write_text("\n".join(json.dumps(w) for w in snap["waves"]))
    buf = io.StringIO()
    trace_dump.render_flight(trace_dump._load_flight(str(jl)), out=buf)
    assert f"{len(snap['waves'])} wave(s)" in buf.getvalue()
