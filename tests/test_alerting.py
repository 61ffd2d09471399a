"""Closed-loop alerting (PR 9): scheduled watcher + SLO engine + health.

Covers the tentpole acceptance paths: a watch with an interval trigger
fires AUTONOMOUSLY through the persistent-task ticker (no manual
_execute), survives an engine restart, throttles duplicate firings and
exposes its alert history through normal search; the SLO engine turns
the PR-4/PR-5 measured signals into objectives whose breach flips the
health indicators; an injected MFU collapse (ES_TPU_PEAK_* override)
flips kernel-utilization and fires the prebuilt SLO watch; and the
3-node cluster e2e — a watch put on node A fires on an injected p99
breach, the alert doc reads back from node C via the replicated
`.alerts-*` index, and `_health_report` on another node diagnoses the
breached objective by name."""

import json
import time

import pytest

from elasticsearch_tpu import xpack
from elasticsearch_tpu.engine import Engine
from elasticsearch_tpu.telemetry import metrics
from elasticsearch_tpu.xpack.watcher import (
    ALERTS_INDEX,
    cron_matches,
    resolve_path,
)


def _wait_until(pred, timeout=20.0, step=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = pred()
        if got:
            return got
        time.sleep(step)
    return pred()


# ---------------------------------------------------------------------------
# helpers: cron subset + greedy dotted paths
# ---------------------------------------------------------------------------

def test_cron_subset_and_greedy_paths():
    t = time.struct_time((2026, 8, 4, 14, 30, 0, 1, 216, 0))  # Tue 14:30
    assert cron_matches("* * * * *", t)
    assert cron_matches("30 14 * * *", t)
    assert cron_matches("*/5 * * * *", t)       # 30 % 5 == 0
    assert cron_matches("0,30 * * * *", t)
    assert cron_matches("25-35 14 * * 2", t)    # Tuesday == cron dow 2
    assert not cron_matches("31 14 * * *", t)
    assert not cron_matches("30 14 * * 0", t)   # not Sunday
    with pytest.raises(Exception):
        cron_matches("* * *", t)
    # metric names contain dots: the resolver must try the LONGEST
    # joinable key first and backtrack
    snap = {"histograms": {"es.rest.request.ms": {"p99": 42.0}},
            "counters": {"a": 1, "a.b": {"c": 2}}}
    assert resolve_path(snap, "histograms.es.rest.request.ms.p99") == 42.0
    assert resolve_path(snap, "counters.a.b.c") == 2
    assert resolve_path(snap, "histograms.nope.p99") is None
    assert resolve_path({"xs": [{"v": 7}]}, "xs.0.v") == 7


# ---------------------------------------------------------------------------
# scheduled firing, throttling, history, restart survival
# ---------------------------------------------------------------------------

def test_interval_watch_fires_autonomously_and_throttles():
    e = Engine(None)
    try:
        e.settings.update({"persistent": {
            "xpack.watcher.tick.interval": "50ms"}})
        xpack.watcher_put(e, "heartbeat", {
            "trigger": {"schedule": {"interval": "10ms"}},
            "input": {"simple": {"beat": 1}},
            "condition": {"always": {}},
            "actions": {"log": {"logging": {"text": "beat"},
                                "throttle_period": "1h"}},
        })
        xpack.watcher_ensure_executor(e)
        assert e.persistent.ticker_stats()["running"]
        st = _wait_until(
            lambda: (e.watcher.counters["executions"] >= 3
                     and e.watcher.counters["throttles"] >= 1
                     and e.watcher.stats()))
        assert st, e.watcher.counters
        # the action ran once, later firings were throttle-deduped
        w = xpack.watcher_get(e, "heartbeat")
        assert w["status"]["alert"]["state"] == "firing"
        acts = w["status"]["actions"]["log"]
        assert acts["ack"]["state"] == "ackable"
        assert acts["last_throttle"]["reason"].startswith("throttled")
        assert e.meta.extras["watcher_log"]["heartbeat"] == ["beat"]
        # alert history is queryable through NORMAL search: one alert doc
        # per watch (transition-written), history docs per execution
        alerts = e.search_multi(ALERTS_INDEX, size=10)["hits"]["hits"]
        by_watch = {h["_source"]["watch_id"]: h["_source"] for h in alerts}
        assert by_watch["heartbeat"]["state"] == "firing"
        hist = e.search_multi(
            ".watcher-history-8-*",
            query={"term": {"watch_id": "heartbeat"}},
            size=100)["hits"]
        assert hist["total"]["value"] >= 3
        states = {h["_source"]["state"] for h in hist["hits"]}
        assert "executed" in states and "throttled" in states
        # the prebuilt SLO watch materialized alongside (closed loop)
        assert "slo-compliance" in e.meta.extras["watches"]
    finally:
        e.close()
    assert not e.persistent.ticker_stats()["running"]


def test_watch_survives_engine_restart(tmp_path):
    data = str(tmp_path / "node")
    e = Engine(data)
    e.settings.update({"persistent": {
        "xpack.watcher.tick.interval": "50ms"}})
    xpack.watcher_put(e, "fast", {
        "trigger": {"schedule": {"interval": "10ms"}},
        "input": {"simple": {"x": 1}},
        "condition": {"always": {}},
        "actions": {},
    })
    xpack.watcher_ensure_executor(e)
    _wait_until(lambda: e.watcher.counters["executions"] >= 1)
    first = e.watcher.counters["executions"]
    assert first >= 1
    e.close()
    # a fresh process: the persisted watcher-driver task restarts the
    # ticker at boot — no request ever touches the watcher surface
    e2 = Engine(data)
    try:
        assert "watcher-driver" in e2.meta.persistent_tasks
        assert _wait_until(lambda: e2.persistent.ticker_stats()["running"])
        assert _wait_until(lambda: e2.watcher.counters["executions"] >= 1), \
            e2.watcher.counters
        w = e2.watcher.get("fast")
        assert w["status"]["alert"]["state"] == "firing"
    finally:
        e2.close()


def test_ack_state_machine_resets_on_resolution():
    e = Engine(None)
    try:
        metrics.reset()
        xpack.watcher_put(e, "gauge-watch", {
            "trigger": {"schedule": {"interval": "10s"}},
            "input": {"metrics": {}},
            "condition": {"compare": {
                "ctx.payload.counters.app.errors": {"gte": 3}}},
            "actions": {"note": {"logging": {"text": "errors"},
                                 "throttle_period": "0s"}},
        })
        # condition not met: ok
        out = xpack.watcher_execute(e, "gauge-watch")
        assert not out["watch_record"]["condition_met"]
        assert out["watch_record"]["alert_state"] == "ok"
        # breach -> firing, action executes
        metrics.counter_inc("app.errors", 3)
        out = xpack.watcher_execute(e, "gauge-watch")
        assert out["watch_record"]["condition_met"]
        assert out["watch_record"]["actions_executed"] == ["note"]
        assert out["watch_record"]["alert_state"] == "firing"
        # ack: still met, but the acked action is skipped
        res = xpack.watcher_ack(e, "gauge-watch")
        assert res["acked"] == ["note"]
        assert res["status"]["alert"]["state"] == "acked"
        out = xpack.watcher_execute(e, "gauge-watch")
        assert out["watch_record"]["condition_met"]
        assert out["watch_record"]["actions_executed"] == []
        assert {t["id"]: t["reason"] for t in
                out["watch_record"]["actions_throttled"]} == {
                    "note": "acked"}
        # resolution re-arms: condition false -> ok + ack reset
        metrics.reset()
        out = xpack.watcher_execute(e, "gauge-watch")
        assert out["watch_record"]["alert_state"] == "ok"
        st = xpack.watcher_get(e, "gauge-watch")["status"]
        assert st["actions"]["note"]["ack"]["state"] == \
            "awaits_successful_execution"
        # ...and the next breach fires + executes again
        metrics.counter_inc("app.errors", 5)
        out = xpack.watcher_execute(e, "gauge-watch")
        assert out["watch_record"]["actions_executed"] == ["note"]
        assert out["watch_record"]["alert_state"] == "firing"
        # alert doc reflects the LATEST transition (one doc per watch)
        doc = e.search_multi(
            ALERTS_INDEX, query={"term": {"watch_id": "gauge-watch"}},
            size=5)["hits"]["hits"]
        assert len(doc) == 1 and doc[0]["_source"]["state"] == "firing"
    finally:
        e.close()


def test_monitoring_input_rides_the_tsdb_agg_path():
    e = Engine(None)
    try:
        e.monitoring.collect_once()
        xpack.watcher_put(e, "mon", {
            "trigger": {"schedule": {"interval": "10s"}},
            "input": {"monitoring": {"body": {
                "size": 0,
                "query": {"term": {"type": "node_stats"}},
                "aggs": {"by_node": {"terms": {"field": "node"}}},
            }}},
            "condition": {"compare": {
                "ctx.payload.hits.total.value": {"gte": 1}}},
            "actions": {},
        })
        out = xpack.watcher_execute(e, "mon")
        assert out["watch_record"]["condition_met"]
        # deactivate gates scheduled firing
        xpack.watcher_activate(e, "mon", False)
        assert e.watcher.run_scheduled() == []
    finally:
        e.close()


# ---------------------------------------------------------------------------
# SLO engine + health indicators
# ---------------------------------------------------------------------------

def test_slo_breach_flips_health_indicator_with_diagnosis():
    e = Engine(None)
    try:
        metrics.reset()
        metrics.histogram_record("es.rest.request.ms", 250.0)
        e.settings.update({"persistent": {"slo.search.p99_ms": 100.0}})
        ev = e.slo.evaluate()
        assert "search-p99-latency" in ev["breached"], ev
        assert not ev["compliant"]
        obj = {o["id"]: o for o in ev["objectives"]}["search-p99-latency"]
        assert obj["measured"] > 100.0 and obj["threshold"] == 100.0
        hr = xpack.health_report(e)
        ind = hr["indicators"]["slo_compliance"]
        assert ind["status"] == "yellow"
        assert "search-p99-latency" in ind["details"]["breached"]
        # the diagnosis NAMES the breached objective (acceptance shape)
        assert "search-p99-latency" in ind["diagnosis"][0]["cause"]
        assert ind["impacts"] and ind["diagnosis"][0]["action"]
        assert hr["status"] == "yellow"
        # gauges for the exposition
        snap = metrics.snapshot()
        assert snap["gauges"]["es.slo.compliant"] == 0
        assert snap["gauges"]["es.health.status"] == 1
        # recovery
        e.settings.update({"persistent": {"slo.search.p99_ms": 1e9}})
        ev = e.slo.evaluate()
        assert ev["compliant"]
        assert xpack.health_report(e)["indicators"][
            "slo_compliance"]["status"] == "green"
    finally:
        e.close()


def test_mfu_collapse_flips_indicator_and_fires_prebuilt_watch(monkeypatch):
    """Acceptance: an injected MFU collapse (ES_TPU_PEAK_* forcing the
    roofline absurdly high, so measured MFU ~ 0) breaches the kernel
    floor, flips kernel-utilization, and the prebuilt SLO watch fires an
    alert into .alerts-default."""
    monkeypatch.setenv("ES_TPU_PEAK_FLOPS", "1e21")
    monkeypatch.setenv("ES_TPU_PEAK_BW", "1e21")
    e = Engine(None)
    try:
        metrics.reset()
        e.settings.update({"persistent": {
            "slo.kernel.floors": json.dumps({"*": {"mfu": 0.5}}),
            "slo.kernel.min_calls": 1,
        }})
        e.create_index("k", {"properties": {"body": {"type": "text"}}})
        idx = e.indices["k"]
        for i in range(8):
            idx.index_doc(str(i), {"body": f"alpha w{i}"})
        idx.refresh()
        for _ in range(3):  # real dispatches record es.kernel.* metrics
            idx.search(query={"match": {"body": "alpha"}})
        ev = e.slo.evaluate()
        kernel_breaches = [o for o in ev["objectives"]
                           if o["kind"] == "kernel"
                           and o["status"] == "breached"]
        assert kernel_breaches, ev["objectives"]
        hr = xpack.health_report(e)
        ind = hr["indicators"]["kernel_utilization"]
        assert ind["status"] == "yellow"
        assert ind["impacts"] and ind["diagnosis"]
        assert "measured" in ind["diagnosis"][0]["cause"]
        # the prebuilt watch materializes + fires on the breach
        xpack.watcher_ensure_executor(e)
        out = xpack.watcher_execute(e, "slo-compliance")
        assert out["watch_record"]["condition_met"]
        assert out["watch_record"]["alert_state"] == "firing"
        doc = e.search_multi(
            ALERTS_INDEX, query={"term": {"watch_id": "slo-compliance"}},
            size=5)["hits"]["hits"]
        assert len(doc) == 1 and doc[0]["_source"]["state"] == "firing"
    finally:
        e.close()


# ---------------------------------------------------------------------------
# REST surface: watcher APIs, /_slo, health derivation, prometheus gauges
# ---------------------------------------------------------------------------

def test_rest_surface_watcher_slo_health_prometheus():
    import asyncio

    async def go():
        from aiohttp.test_utils import TestClient, TestServer

        from elasticsearch_tpu.rest.app import make_app

        client = TestClient(TestServer(make_app()))
        await client.start_server()
        engine = client.server.app["engine"]
        try:
            r = await client.put("/_watcher/watch/w1", json={
                "trigger": {"schedule": {"interval": "1h"}},
                "input": {"simple": {"v": 1}},
                "condition": {"always": {}},
                "actions": {"log": {"logging": {"text": "x"}}},
            })
            assert r.status == 200 and (await r.json())["created"]
            r = await client.post("/_watcher/watch/w1/_execute")
            rec = (await r.json())["watch_record"]
            assert rec["condition_met"] and rec["actions_executed"] == ["log"]
            r = await client.post("/_watcher/watch/w1/_ack")
            assert (await r.json())["acked"] == ["log"]
            r = await client.post("/_watcher/watch/w1/_deactivate")
            assert not (await r.json())["status"]["state"]["active"]
            r = await client.post("/_watcher/watch/w1/_activate")
            assert (await r.json())["status"]["state"]["active"]
            r = await client.get("/_watcher/stats")
            st = await r.json()
            assert st["stats"][0]["watch_count"] >= 1
            assert st["stats"][0]["counters"]["executions"] >= 1
            # PUT through REST started the scheduler
            assert st["stats"][0]["ticker"]["running"] is True
            r = await client.get("/_slo?evaluate=true")
            slo = (await r.json())["slo"]
            assert slo["objective_count"] >= 1
            # health report: >= 8 indicators, each with status + symptom
            r = await client.get("/_health_report")
            hr = await r.json()
            assert len(hr["indicators"]) >= 8
            for ind in hr["indicators"].values():
                assert ind["status"] and ind["symptom"]
            for name in ("kernel_utilization", "slo_compliance", "hbm",
                         "serving_backpressure", "breakers", "watcher"):
                assert name in hr["indicators"], name
            # cluster health derives from searcher/replica state: an
            # index with replicas on a single node is YELLOW, and the
            # report's shards indicator agrees
            await client.put("/hy", json={
                "settings": {"number_of_replicas": 1}})
            r = await client.get("/_cluster/health")
            h = await r.json()
            assert h["status"] == "yellow"
            assert h["unassigned_shards"] == 1
            r = await client.get("/_cluster/health?level=indices")
            assert (await r.json())["indices"]["hy"]["status"] == "yellow"
            r = await client.get("/_health_report")
            assert (await r.json())["indicators"][
                "shards_availability"]["status"] == "yellow"
            r = await client.get("/_cat/indices?format=json")
            rows = {row["index"]: row for row in await r.json()}
            assert rows["hy"]["health"] == "yellow"
            assert rows["hy"]["rep"] == "1"
            # wait_for_status that cannot be met: 408 + timed_out
            r = await client.get(
                "/_cluster/health?wait_for_status=green&timeout=200ms")
            assert r.status == 408 and (await r.json())["timed_out"]
            # ...and one that is already met returns immediately
            r = await client.get(
                "/_cluster/health?wait_for_status=yellow&timeout=200ms")
            assert r.status == 200
            await client.delete("/hy")
            r = await client.get("/_cluster/health")
            assert (await r.json())["status"] == "green"
            # prometheus exposition: HELP/TYPE lines + the health/slo
            # gauges (the parser enforces HELP-before-TYPE)
            from tests.test_observability import _parse_prometheus

            r = await client.get("/_prometheus/metrics")
            types, samples = _parse_prometheus(await r.text())
            names = {n for n, _l, _v in samples}
            assert "es_health_status" in names
            assert "es_slo_compliant" in names
            assert types["es_health_status"] == "gauge"
            assert ("es_health_status", None, 0.0) in samples
            assert ("es_slo_compliant", None, 1.0) in samples
            # stop the scheduler through the API
            r = await client.post("/_watcher/_stop")
            assert (await r.json())["acknowledged"]
        finally:
            await client.close()
            engine.persistent.stop_ticker()

    asyncio.run(go())


# ---------------------------------------------------------------------------
# bench-regression lint (scripts/bench_regress.py)
# ---------------------------------------------------------------------------

def test_bench_regress_compare(tmp_path):
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "bench_regress", os.path.join(os.path.dirname(__file__), "..",
                                      "scripts", "bench_regress.py"))
    br = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(br)
    prev = {"extras": {"c1": {
        "qps": 100.0, "latency_pcts": {"p99_ms": 50.0},
        "profile": {"device_utilization": {
            "device_kind": "tpu-v5e",
            "kernels": {"fused.scan": {"mfu": 0.10, "bw_util": 0.5}}}},
        "only_in_prev": {"qps": 9.0},
    }}}
    latest = {"extras": {"c1": {
        "qps": 70.0,                                  # -30%: regressed
        "latency_pcts": {"p99_ms": 55.0},             # +10%: fine
        "profile": {"device_utilization": {
            "device_kind": "tpu-v5e",
            "kernels": {"fused.scan": {"mfu": 0.09,   # -10%: fine
                                       "bw_util": 0.2}}}},  # -60%: regressed
        "new_config": {"qps": 1.0},
    }}}
    regressions, improvements, compared = br.compare(prev, latest, 0.2)
    reg_paths = {p for p, *_ in regressions}
    assert reg_paths == {
        "c1.qps",
        "c1.profile.device_utilization.kernels.fused.scan.bw_util"}
    assert compared == 4  # only paths present in both records
    # end-to-end through main(): TPU records ENFORCE (exit 1)
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(prev))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(latest))
    assert br.main(["--dir", str(tmp_path)]) == 1
    # CPU smokes are advisory (BENCH_NOTES: host-bound, non-criteria)
    for rec in (prev, latest):
        rec["extras"]["c1"]["profile"]["device_utilization"][
            "device_kind"] = "cpu"
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(prev))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(latest))
    assert br.main(["--dir", str(tmp_path)]) == 0
    assert br.main(["--dir", str(tmp_path), "--force"]) == 1
    # fewer than two records: nothing to do
    (tmp_path / "BENCH_r01.json").unlink()
    assert br.main(["--dir", str(tmp_path)]) == 0


# ---------------------------------------------------------------------------
# 3-node cluster e2e: watch on node A -> alert readable from node C,
# health diagnosis on any node
# ---------------------------------------------------------------------------

def _http(method, port, path, body=None, timeout=60.0):
    import urllib.error
    import urllib.request

    data = None
    headers = {}
    if body is not None:
        data = (body if isinstance(body, str)
                else json.dumps(body)).encode()
        headers["Content-Type"] = "application/json"
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, headers=headers,
        method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read() or b"{}")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def test_cluster_e2e_scheduled_watch_alert_and_health():
    from elasticsearch_tpu.cluster.http import HttpGateway, wait_for_http
    from elasticsearch_tpu.cluster.server import NodeServer

    ids = ["w1", "w2", "w3"]
    servers = {nid: NodeServer(nid, ids, {}, port=0) for nid in ids}
    for nid, s in servers.items():
        for other, o in servers.items():
            if other != nid:
                s.network.add_peer(other, "127.0.0.1", o.port)
    gateways = {}
    try:
        for nid, s in servers.items():
            s.start()
            gateways[nid] = HttpGateway(s, surface="full").start()
        port_a = gateways["w1"].port
        wait_for_http(port_a, lambda h: h.get("master_node")
                      and h.get("number_of_nodes") == 3)
        # inject the p99 breach: a replicated settings op arms an SLO
        # objective every node must breach (the shared in-process
        # registry already holds REST latency samples from the requests
        # themselves)
        st, r = _http("PUT", port_a, "/_cluster/settings", {
            "persistent": {
                "xpack.watcher.tick.interval": "200ms",
                "slo.search.p99_ms": 0.0001,
            }}, timeout=90.0)
        assert st == 200, r
        # the watch lands on node A; the PUT replicates, every node's
        # scheduler starts, and ONLY the elected master fires it
        st, r = _http("PUT", port_a, "/_watcher/watch/p99-breach", {
            "trigger": {"schedule": {"interval": "200ms"}},
            "input": {"slo": {}},
            "condition": {"compare": {
                "ctx.payload.breached_count": {"gt": 0}}},
            "actions": {"note": {"logging": {"text": "p99 breach"},
                                 "throttle_period": "5s"}},
        }, timeout=90.0)
        assert st == 200, r
        # the alert doc must become readable from node C through NORMAL
        # search on the replicated .alerts-default index
        port_c = gateways["w3"].port
        deadline = time.time() + 90.0
        alert = None
        while time.time() < deadline:
            st, res = _http("POST", port_c, "/.alerts-default/_search", {
                "query": {"term": {"watch_id": "p99-breach"}},
                "size": 5}, timeout=90.0)
            if st == 200:
                hits = res.get("hits", {}).get("hits", [])
                if hits and hits[0]["_source"]["state"] == "firing":
                    alert = hits[0]["_source"]
                    break
            time.sleep(0.5)
        assert alert is not None, "alert doc never replicated to node C"
        assert alert["watch_id"] == "p99-breach"
        # execution history replicated too: the master exports it after the
        # alert doc, as an op of its own, so it may reach node C a tick later
        while True:
            st, res = _http("POST", port_c, "/.watcher-history-8-*/_search", {
                "query": {"term": {"watch_id": "p99-breach"}}, "size": 1},
                timeout=90.0)
            if (st == 200 and res["hits"]["total"]["value"] >= 1) \
                    or time.time() >= deadline:
                break
            time.sleep(0.5)
        assert st == 200 and res["hits"]["total"]["value"] >= 1, res
        # _health_report on ANOTHER node: the fan-out merges every
        # node's indicators; slo-compliance is yellow and its diagnosis
        # names the breached objective
        st, hr = _http("GET", gateways["w2"].port, "/_health_report",
                       timeout=90.0)
        assert st == 200, hr
        assert set(hr["nodes"]) == set(ids), hr.get("failures")
        ind = hr["indicators"]["slo_compliance"]
        assert ind["status"] == "yellow", ind
        assert "search-p99-latency" in ind["diagnosis"][0]["cause"]
        assert set(ind["nodes"]) == set(ids)
        assert hr["status"] in ("yellow", "red")
        assert len(hr["indicators"]) >= 8
        # disarm before teardown (replicated)
        _http("PUT", port_a, "/_cluster/settings", {
            "persistent": {"slo.search.p99_ms": 1e9}}, timeout=90.0)
        _http("POST", port_a, "/_watcher/_stop", timeout=90.0)
    finally:
        for g in gateways.values():
            g.close()
        for s in servers.values():
            s.close()
