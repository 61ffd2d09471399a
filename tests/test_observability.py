"""End-to-end tracing + device-cost profiling + metrics (PR 4).

Covers the observability tentpole: W3C traceparent propagation through the
REST layer and across the TCP transport of a 3-node cluster (one search ->
one trace_id on every involved node), `"profile": true` device sections
with kernel wall timings for the fused and escalated tiers, exponential-
bucket histogram percentiles against numpy, the Prometheus exposition
endpoint (hand-rolled text-format parser — no new dependency), hot
threads, and slowlog trace enrichment."""

import asyncio
import json
import re
import threading

import numpy as np
import pytest

from elasticsearch_tpu import telemetry
from elasticsearch_tpu.telemetry import (
    MetricsRegistry,
    TraceContext,
    activate_trace,
    collect_profile_events,
    format_traceparent,
    parse_traceparent,
    stitch_trace,
)


# ---------------------------------------------------------------------------
# histograms / metrics registry
# ---------------------------------------------------------------------------

def test_histogram_percentiles_against_numpy():
    """Exponential buckets are 2^(1/4) wide, so estimates must land
    within ~19% relative of numpy's exact percentiles (plus in-bucket
    interpolation slack) across very differently shaped distributions."""
    rng = np.random.default_rng(42)
    for sample in (
        rng.lognormal(mean=2.0, sigma=1.0, size=5000),     # heavy tail
        rng.uniform(0.5, 200.0, size=5000),                # flat
        rng.exponential(scale=30.0, size=5000) + 0.01,     # decaying
    ):
        m = MetricsRegistry()
        for v in sample:
            m.histogram_record("lat", float(v))
        h = m.snapshot()["histograms"]["lat"]
        for q, key in ((50, "p50"), (90, "p90"), (99, "p99")):
            exact = float(np.percentile(sample, q))
            assert abs(h[key] - exact) <= 0.25 * exact, (
                q, h[key], exact)
        assert h["min"] == pytest.approx(sample.min())
        assert h["max"] == pytest.approx(sample.max())
        assert h["p50"] <= h["p90"] <= h["p99"] <= h["max"]


def test_histogram_zero_and_negative_values():
    m = MetricsRegistry()
    for v in (-1.0, 0.0, 0.0, 5.0):
        m.histogram_record("h", v)
    h = m.snapshot()["histograms"]["h"]
    assert h["count"] == 4 and h["min"] == -1.0 and h["max"] == 5.0
    assert h["p50"] <= h["p99"] <= 5.0


def test_metrics_registry_thread_safety():
    """Concurrent read-modify-writes from many threads must lose nothing
    (the pre-PR-4 plain-dict registry dropped updates under the aiohttp
    handler + transport-thread mix)."""
    m = MetricsRegistry()
    n_threads, n_each = 8, 2000

    def work():
        for i in range(n_each):
            m.counter_inc("ops")
            m.histogram_record("lat", float(i % 97) + 0.5)
            m.gauge_set("last", i)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = m.snapshot()
    assert snap["counters"]["ops"] == n_threads * n_each
    assert snap["histograms"]["lat"]["count"] == n_threads * n_each
    m.reset()
    assert m.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


_PROM_LINE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? "
    r"(-?(?:\d+\.?\d*(?:[eE][+-]?\d+)?|[+-]?Inf|NaN))$")


def _parse_prometheus(text):
    """Hand-rolled text-format 0.0.4 parser with the semantics
    prometheus_client enforces: every non-comment line is
    `name[{labels}] value`, HELP then TYPE declarations precede their
    samples, histogram buckets are cumulative and end at
    +Inf == _count."""
    types = {}
    helps = {}
    samples = []
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            assert parts[1] in ("TYPE", "HELP"), line
            if parts[1] == "TYPE":
                types[parts[2]] = parts[3]
                # prometheus_client emits HELP before TYPE per family
                assert parts[2] in helps, f"TYPE before HELP: {line!r}"
            else:
                assert len(parts) == 4 and parts[3].strip(), (
                    f"HELP without text: {line!r}")
                helps[parts[2]] = parts[3]
            continue
        mo = _PROM_LINE.match(line)
        assert mo, f"unparseable exposition line: {line!r}"
        samples.append((mo.group(1), mo.group(2), float(mo.group(3))))
    # histogram sanity: cumulative buckets, +Inf last and == _count
    for name, kind in types.items():
        if kind != "histogram":
            continue
        buckets = [(lab, v) for n, lab, v in samples
                   if n == f"{name}_bucket"]
        assert buckets and buckets[-1][0] == '{le="+Inf"}', name
        counts = [v for _lab, v in buckets]
        assert counts == sorted(counts), f"{name} buckets not cumulative"
        (count,) = [v for n, _lab, v in samples if n == f"{name}_count"]
        assert buckets[-1][1] == count
    return types, samples


def test_prometheus_text_rendering_unit():
    m = MetricsRegistry()
    m.counter_inc("es.search.query.total", 3)
    m.gauge_set("jobs.open", 2)
    m.gauge_set("weird name-with chars!", lambda: 7)
    for v in (0.5, 1.0, 2.0, 100.0):
        m.histogram_record("es.rest.request.ms", v)
    types, samples = _parse_prometheus(
        m.prometheus_text({"extra.gauge": 4, "skipped": "not-a-number"}))
    assert types["es_search_query_total"] == "counter"
    assert ("es_search_query_total", None, 3.0) in samples
    assert ("extra_gauge", None, 4.0) in samples
    assert types["es_rest_request_ms"] == "histogram"
    assert not any(n == "skipped" for n, _l, _v in samples)


# ---------------------------------------------------------------------------
# trace context plumbing
# ---------------------------------------------------------------------------

def test_traceparent_parse_and_format():
    tid, sid = "4bf92f3577b34da6a3ce929d0e0e4736", "00f067aa0ba902b7"
    assert parse_traceparent(f"00-{tid}-{sid}-01") == (tid, sid)
    assert parse_traceparent(format_traceparent(tid, sid)) == (tid, sid)
    for bad in (None, "", "garbage", "00-zz-xx-01",
                f"00-{'0' * 32}-{sid}-01"):
        assert parse_traceparent(bad) is None


def test_spans_join_activated_trace_across_threads():
    ctx = TraceContext(trace_id=telemetry.new_trace_id(), task_id="op-7")
    with activate_trace(ctx, node="n-test"):
        with telemetry.TRACER.span("outer") as outer:
            import contextvars

            cc = contextvars.copy_context()

            def child():
                with telemetry.TRACER.span("inner"):
                    pass

            # the engine-worker / transport-offload pattern: contextvars
            # copied onto another thread keep the span parentage
            t = threading.Thread(target=lambda: cc.run(child))
            t.start()
            t.join()
    assert outer.trace_id == ctx.trace_id
    assert outer.node == "n-test"
    spans = telemetry.TRACER.spans_for_trace(ctx.trace_id)
    names = {s["name"] for s in spans}
    assert {"outer", "inner"} <= names
    inner = next(s for s in spans if s["name"] == "inner")
    assert inner["parent_span_id"] == outer.span_id


def test_stitch_trace_dedupes_and_nests():
    a = {"name": "root", "trace_id": "t", "span_id": "a",
         "parent_span_id": None, "node": "n1", "start_unix": 1.0,
         "duration_ms": 10.0, "attributes": {}}
    b = {"name": "child", "trace_id": "t", "span_id": "b",
         "parent_span_id": "a", "node": "n2", "start_unix": 1.002,
         "duration_ms": 5.0, "attributes": {}}
    out = stitch_trace([a, b, dict(b)])  # duplicate collected twice
    assert out["span_count"] == 2
    assert out["nodes"] == ["n1", "n2"]
    assert len(out["spans"]) == 1
    assert out["spans"][0]["children"][0]["name"] == "child"


# ---------------------------------------------------------------------------
# REST: tracing, profile device sections, prometheus, hot threads
# ---------------------------------------------------------------------------

async def _drive_rest():
    from aiohttp.test_utils import TestClient, TestServer

    from elasticsearch_tpu.rest.app import make_app

    client = TestClient(TestServer(make_app()))
    await client.start_server()
    return client


def _run(coro):
    return asyncio.run(coro)


def test_rest_trace_propagation_slowlog_and_trace_endpoint():
    async def go():
        client = await _drive_rest()
        try:
            await client.put("/slowt", json={
                "mappings": {"properties": {"x": {"type": "text"}}},
                "settings": {"search.slowlog.threshold.query.warn": "0ms"},
            })
            await client.put("/slowt/_doc/1?refresh=true",
                             json={"x": "hello"})
            tid = telemetry.new_trace_id()
            telemetry.recent_slowlogs.clear()
            r = await client.post(
                "/slowt/_search",
                json={"query": {"match": {"x": "hello"}}},
                headers={
                    "traceparent": format_traceparent(tid, "00f067aa0ba902b7"),
                    "X-Opaque-Id": "client-123",
                })
            assert r.status == 200
            # the accepted trace id is echoed back
            assert r.headers["X-Trace-Id"] == tid
            assert parse_traceparent(r.headers["traceparent"])[0] == tid
            # slowlog entries are joinable against the trace
            entry = [e for e in telemetry.recent_slowlogs
                     if e["index"] == "slowt"][-1]
            assert entry["trace_id"] == tid
            assert entry["task_id"] == "client-123"
            assert entry["node"] == "node-0"
            # /_trace/{id} stitches http root + engine query-phase child
            r = await client.get(f"/_trace/{tid}")
            assert r.status == 200
            trace = await r.json()
            assert trace["trace_id"] == tid

            def names(spans):
                for s in spans:
                    yield s["name"]
                    yield from names(s["children"])

            got = set(names(trace["spans"]))
            assert any(n.startswith("http POST") for n in got), got
            assert "executeQueryPhase" in got
            r = await client.get(f"/_trace/{'ab' * 16}")
            assert r.status == 404
            # _nodes/stats surfaces slowlogs + recent spans
            stats = await (await client.get("/_nodes/stats")).json()
            tel = stats["nodes"]["node-0"]["telemetry"]
            assert any(e.get("trace_id") == tid
                       for e in tel["recent_slowlogs"])
            assert any(s["trace_id"] == tid for s in tel["recent_spans"])
        finally:
            await client.close()

    _run(go())


def test_rest_profile_sharded_device_sections():
    async def go():
        client = await _drive_rest()
        try:
            await client.put("/profi", json={
                "mappings": {"properties": {"body": {"type": "text"}}},
                "settings": {"number_of_shards": 4},
            })
            lines = []
            for i in range(40):
                lines.append(json.dumps({"index": {"_id": str(i)}}))
                lines.append(json.dumps(
                    {"body": f"alpha beta w{i % 7} gamma"}))
            await client.post("/profi/_bulk?refresh=true",
                              data="\n".join(lines) + "\n",
                              headers={"Content-Type": "application/json"})
            body = {"query": {"match": {"body": "alpha"}}, "profile": True}
            res = await (await client.post("/profi/_search",
                                           json=body)).json()
            shards = res["profile"]["shards"]
            # per-shard entries for the sharded path ([node][index][shard])
            assert len(shards) == 4
            ids = [s["id"] for s in shards]
            assert ids == [f"[node-0][profi][{i}]" for i in range(4)]
            for s in shards:
                dev = s["device"]
                assert dev["tier"], dev
                assert dev["kernels"], "kernel-level timings missing"
                for kern in dev["kernels"]:
                    assert kern["time_in_nanos"] >= 0
                    assert kern["name"]
                assert set(dev["request_cache"]) == {"hits", "misses"}
                assert s["phases"]["query_ms"] >= 0
                # the classic measured query tree is still there
                assert s["searches"][0]["query"][0]["breakdown"][
                    "score_count"] == 1
            # a repeat of the same profiled search is served by the
            # request cache — visible in the device section. The FIRST
            # profiled request's tree walk merges the tiered searcher
            # (pre-existing: profiling uses the merged view), which rolls
            # the cache identity once — so warmth shows from request 3 on.
            await client.post("/profi/_search", json=body)
            res3 = await (await client.post("/profi/_search",
                                            json=body)).json()
            dev3 = res3["profile"]["shards"][0]["device"]
            from elasticsearch_tpu.cache import request_cache

            if request_cache().enabled:  # off under the shuffled-order gate
                assert dev3["request_cache"]["hits"] >= 1
        finally:
            await client.close()

    _run(go())


def test_rest_prometheus_endpoint_scrapes():
    async def go():
        client = await _drive_rest()
        try:
            await client.put("/prom", json={
                "mappings": {"properties": {"x": {"type": "text"}}}})
            await client.put("/prom/_doc/1?refresh=true", json={"x": "hi"})
            await client.post("/prom/_search",
                              json={"query": {"match": {"x": "hi"}}})
            r = await client.get("/_prometheus/metrics")
            assert r.status == 200
            assert r.content_type == "text/plain"
            types, samples = _parse_prometheus(await r.text())
            names = {n for n, _l, _v in samples}
            # counters, gauges, histograms, breaker + cache state
            assert "es_search_query_total" in names
            assert types["es_search_query_took_ms"] == "histogram"
            assert types["es_rest_request_ms"] == "histogram"
            assert any(n.startswith("es_breaker_parent_") for n in names)
            assert "es_request_cache_memory_size_in_bytes" in names
        finally:
            await client.close()

    _run(go())


def test_rest_hot_threads():
    async def go():
        client = await _drive_rest()
        try:
            r = await client.get(
                "/_nodes/hot_threads?threads=2&snapshots=3&interval=10ms")
            assert r.status == 200
            text = await r.text()
            assert "Hot threads" in text
            assert "busy samples" in text
            assert "thread '" in text  # at least one named thread reported
        finally:
            await client.close()

    _run(go())


# ---------------------------------------------------------------------------
# device-cost collector: fused + escalated kernel timings
# ---------------------------------------------------------------------------

@pytest.fixture()
def fused_corpus(monkeypatch):
    monkeypatch.setenv("ES_TPU_FUSED", "force")
    from elasticsearch_tpu.index.mappings import Mappings
    from elasticsearch_tpu.index.pack import PackBuilder
    from elasticsearch_tpu.ops.batched import BatchTermSearcher
    from elasticsearch_tpu.query.executor import ShardSearcher

    rng = np.random.default_rng(11)
    m = Mappings({"properties": {"body": {"type": "text"}}})
    b = PackBuilder(m)
    zipf = 1.0 / np.arange(1, 121)
    zipf /= zipf.sum()
    for _ in range(600):
        ln = max(3, int(rng.poisson(10)))
        text = " ".join(f"t{t}" for t in rng.choice(120, size=ln, p=zipf))
        b.add_document(m.parse_document({"body": text}))
    pack = b.build(dense_min_df=32)
    searcher = ShardSearcher(pack, mappings=m)
    return BatchTermSearcher(searcher), rng


def test_profile_events_fused_tier(fused_corpus):
    bs, rng = fused_corpus
    queries = [[(f"t{t}", 1.0) for t in rng.integers(0, 120, size=3)]
               for _ in range(8)]
    with collect_profile_events() as events:
        bs.msearch("body", queries, 5)
    kernels = [e for e in events if e["kind"] == "kernel"]
    assert any(e["kernel"] == "fused.msearch" for e in kernels), events
    assert any(e["kernel"] == "fused.pallas_scan" for e in kernels), events
    assert all(e["ms"] >= 0 for e in kernels)
    tiers = {e["tier"] for e in events if e["kind"] == "tier"}
    assert "fused" in tiers


def test_profile_events_exact_escalation(fused_corpus):
    """A flagged query re-runs on the legacy exact arm; the collector must
    attribute both the escalation tier and its kernel timing (driven
    through _finish with a synthetic flag — organic flags are ~1e-3)."""
    bs, rng = fused_corpus
    queries = [[("t0", 1.0), ("t5", 1.0)], [("t1", 1.0)]]
    k = 5
    fs = bs._fused_searcher(k)
    assert fs is not None
    scores, ids, totals, flagged = fs._run_pass("body", queries, k)
    flagged = np.array([True, False])
    with collect_profile_events() as events:
        s2, i2, t2, first_ok = fs._finish(
            "body", queries, k, scores.copy(), ids.copy(), totals.copy(),
            flagged)
    assert not first_ok[0] and first_ok[1]
    tiers = [e for e in events if e["kind"] == "tier"]
    assert any(e["tier"] == "exact_escalation" and e["queries"] == 1
               for e in tiers), events
    assert any(e["kind"] == "kernel" and e["kernel"] == "batched.escalation"
               for e in events), events


def test_device_sections_shard_attribution():
    from elasticsearch_tpu.search.profile import device_sections

    events = [
        {"kind": "kernel", "kernel": "sharded.spmd_topk", "ms": 2.5},
        {"kind": "tier", "tier": "fused", "queries": 4},
        {"kind": "cache", "shard": 1, "hits": 3, "misses": 1},
        {"kind": "tier", "tier": "exact_escalation", "queries": 1},
    ]
    out = device_sections(events, 2)
    assert len(out) == 2
    # mesh-scoped kernel replicated to both shards
    assert all(s["kernels"][0]["scope"] == "mesh" for s in out)
    # shard-scoped cache event attributed only to shard 1
    assert out[0]["request_cache"] == {"hits": 0, "misses": 0}
    assert out[1]["request_cache"] == {"hits": 3, "misses": 1}
    # escalation outranks the fused arm as the dominant tier
    assert all(s["tier"] == "exact_escalation" for s in out)
    assert out[0]["tiers"] == {"fused": 4, "exact_escalation": 1}


# ---------------------------------------------------------------------------
# 3-node cluster: one search -> one trace_id on every involved node
# ---------------------------------------------------------------------------

def _http(port, method, path, body=None, headers=None):
    """urllib helper returning (status, json, response headers) — the
    cluster-gateway client with header support (trace propagation)."""
    import urllib.error
    import urllib.request

    data = None
    hdrs = dict(headers or {})
    if body is not None:
        data = (body if isinstance(body, str) else json.dumps(body)).encode()
        hdrs.setdefault("Content-Type", "application/json")
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, headers=hdrs,
        method=method)
    try:
        with urllib.request.urlopen(req, timeout=60.0) as r:
            return r.status, json.loads(r.read() or b"{}"), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def test_cluster_trace_propagation_e2e():
    """The acceptance path: a search through a 3-node TCP cluster's
    gateway carries ONE trace_id (supplied as a W3C traceparent) into the
    shard-search spans on every node that served a shard, and
    GET /_trace/{id} stitches them back into one tree."""
    from elasticsearch_tpu.cluster.http import HttpGateway, wait_for_http
    from elasticsearch_tpu.cluster.server import NodeServer

    ids = ["tr1", "tr2", "tr3"]
    servers = {nid: NodeServer(nid, ids, {}, port=0) for nid in ids}
    for nid, s in servers.items():
        for other, o in servers.items():
            if other != nid:
                s.network.add_peer(other, "127.0.0.1", o.port)
    gateways = {}
    try:
        for nid, s in servers.items():
            s.start()
            gateways[nid] = HttpGateway(s).start()
        port = gateways["tr1"].port
        wait_for_http(port, lambda h: h.get("master_node")
                      and h.get("number_of_nodes") == 3)
        st, r, _h = _http(port, "PUT", "/tr", {
            "settings": {"number_of_shards": 3, "number_of_replicas": 0},
            "mappings": {"properties": {"body": {"type": "text"}}},
        })
        assert st == 200, r
        wait_for_http(port, lambda h: h.get("active_shards") == 3
                      and h.get("unassigned_shards") == 0)
        bulk_lines = []
        for i in range(12):
            bulk_lines.append(json.dumps({"index": {"_id": f"d{i}"}}))
            bulk_lines.append(json.dumps({"body": "alpha beta"}))
        st, r, _h = _http(port, "POST", "/tr/_bulk",
                          "\n".join(bulk_lines) + "\n",
                          headers={"Content-Type": "application/x-ndjson"})
        assert st == 200 and not r.get("errors"), r

        tid = "4bf92f3577b34da6a3ce929d0e0e4736"
        st, res, hdrs = _http(
            port, "POST", "/tr/_search",
            {"query": {"match": {"body": "alpha"}}},
            headers={"traceparent": format_traceparent(
                tid, "00f067aa0ba902b7")})
        assert st == 200, res
        assert res["hits"]["total"]["value"] == 12
        assert hdrs.get("X-Trace-Id") == tid

        st, trace, _h = _http(port, "GET", f"/_trace/{tid}")
        assert st == 200, trace
        assert trace["trace_id"] == tid

        flat = []

        def visit(s):
            flat.append(s)
            for c in s.get("children", []):
                visit(c)

        for root in trace["spans"]:
            visit(root)
        assert all(s["trace_id"] == tid for s in flat)
        shard_spans = [s for s in flat if s["name"] == "shardSearchPhase"]
        # every shard of the index produced a trace-joined span...
        assert {s["attributes"]["shard"] for s in shard_spans} == {0, 1, 2}
        # ...on the node that actually served it; with 3 shards balanced
        # over 3 nodes the trace must cross node boundaries
        involved = {s["node"] for s in shard_spans}
        assert len(involved) >= 2, trace["nodes"]
        assert involved <= set(ids)
        assert any(s["name"].startswith("http POST") for s in flat)
        # the gateway's own scrape endpoint carries the REST histogram
        import urllib.request

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/_prometheus/metrics",
                timeout=30.0) as pr:
            types, samples = _parse_prometheus(pr.read().decode())
        assert types.get("es_rest_request_ms") == "histogram"
    finally:
        for g in gateways.values():
            g.close()
        for s in servers.values():
            s.close()
