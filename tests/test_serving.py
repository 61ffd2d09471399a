"""Continuous-batching serving front end: coalescing parity, tenant
fairness, deadlines, queued-task cancellation, and backpressure.

The serving contract under test (serving/): a wave-coalesced request's
response is BYTE-IDENTICAL to solo execution; a heavy tenant can slow a
light one but never block it; deadline-expired entries resolve timed_out
without a device round-trip; cancelling a queued task removes it from
the queue; and overload sheds 429 + Retry-After instead of growing
without bound.
"""

import asyncio
import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor, wait

import numpy as np
import pytest

from elasticsearch_tpu.common.breaker import CircuitBreakingError
from elasticsearch_tpu.engine.engine import Engine
from elasticsearch_tpu.serving import (
    PendingSearch, ServingRejectedError, TenantQueues, parse_tenant_weights,
)
from elasticsearch_tpu.tasks import TaskCancelledException

WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta"]


def _fill(idx, n=60, dims=None):
    for i in range(n):
        doc = {"title": f"{WORDS[i % 7]} {WORDS[(i + 2) % 7]} common",
               "tag": WORDS[i % 3]}
        if dims:
            doc["v"] = [float(i % 3), 1.0, float(i % 5), float(i % 4)][:dims]
        idx.index_doc(str(i), doc)
    idx.refresh()


@pytest.fixture
def engine(tmp_path):
    e = Engine(str(tmp_path / "data"))
    yield e
    e.close()


@pytest.fixture
def served(engine):
    """Engine with one populated index and a live serving service."""
    idx = engine.create_index("idx", {"properties": {
        "title": {"type": "text"}, "tag": {"type": "keyword"},
        "v": {"type": "dense_vector", "dims": 4}}})
    _fill(idx, 60, dims=4)
    svc = engine.serving
    yield engine, idx, svc
    svc.stop()


def _bodies():
    return [
        {"query": {"match": {"title": "alpha"}}, "size": 5},
        {"query": {"match": {"title": "beta gamma"}}, "size": 3},
        {"query": {"term": {"tag": "beta"}}, "size": 4},
        {"query": {"bool": {"should": [{"term": {"title": "alpha"}},
                                       {"term": {"title": "delta"}}]}},
         "size": 6},
        {"query": {"match": {"title": "common"}}, "size": 10,
         "aggs": {"t": {"terms": {"field": "tag"}}}},
        {"knn": {"field": "v", "query_vector": [1, 1, 2, 1], "k": 5,
                 "num_candidates": 20}, "size": 5},
        {"query": {"match_all": {}}, "size": 2, "from": 3},
        {"query": {"match": {"title": "epsilon"}}, "size": 5,
         "track_total_hits": False},
    ]


def _solo(engine, b):
    return engine.search_multi(
        "idx", query=b.get("query"), knn=b.get("knn"),
        size=b.get("size", 10), from_=b.get("from", 0), aggs=b.get("aggs"),
        track_total_hits=b.get("track_total_hits"))


# ---- coalescing parity ---------------------------------------------------


def test_mixed_shape_wave_parity(served):
    """Every wave-eligible request shape — term lane, generic, aggs,
    knn-only, paginated — resolves byte-identical to solo execution."""
    engine, _idx, svc = served
    bodies = _bodies()
    solo = [json.dumps(_solo(engine, b), sort_keys=True) for b in bodies]
    entries = [svc.classify("idx", b, {}) for b in bodies]
    assert all(e is not None for e in entries)
    futs = [svc.submit(e, tenant=f"t{i % 3}") for i, e in enumerate(entries)]
    wait(futs, timeout=120)
    for f, s in zip(futs, solo):
        assert json.dumps(f.result(timeout=1), sort_keys=True) == s
    st = svc.stats()
    assert st["completed"] == len(bodies)
    assert st["waves"] <= st["dispatched"]  # at least some coalescing ran


def test_term_wave_parity_and_occupancy(engine):
    """msearch_wave pads to the compiled power-of-two tier; each real
    query's row is byte-identical to a solo 1-query wave, and the pad is
    reported as the occupancy denominator."""
    from elasticsearch_tpu.ops.batched import BatchTermSearcher
    from elasticsearch_tpu.parallel.sharded import msearch_wave

    idx = engine.create_index("t", {"properties": {
        "title": {"type": "text"}}})
    _fill(idx, 80)
    ss = idx.searcher  # force-merge the tiers: term lane needs one base
    assert BatchTermSearcher.wave_q_tier(1) == 1
    assert BatchTermSearcher.wave_q_tier(3) == 4
    assert BatchTermSearcher.wave_q_tier(4) == 4
    assert BatchTermSearcher.wave_q_tier(5) == 8
    queries = [[("alpha", 1.0)], [("beta", 1.0), ("gamma", 2.0)],
               [("common", 1.0)]]
    (v, s, d, t), tier = msearch_wave(ss, "title", queries, k=5)
    assert tier == 4 and v.shape[0] == 3
    for qi, q in enumerate(queries):
        (v1, s1, d1, t1), tier1 = msearch_wave(ss, "title", [q], k=5)
        assert tier1 == 1
        assert np.array_equal(v[qi], v1[0], equal_nan=True)
        assert np.array_equal(s[qi], s1[0]) and np.array_equal(d[qi], d1[0])
        assert t[qi] == t1[0]


def test_classifier_rejects_out_of_scope(served):
    """Requests the wave lanes don't replicate must classify to None (and
    so ride the classic path) — never misroute, never raise."""
    engine, _idx, svc = served
    assert svc.classify("idx", {"query": {"match_all": {}},
                                "sort": [{"tag": "asc"}]}, {}) is None
    assert svc.classify("idx", {"suggest": {"s": {}}}, {}) is None
    assert svc.classify("idx", {"query": {"match_all": {}}},
                        {"scroll": "1m"}) is None
    assert svc.classify("idx", {"profile": True,
                                "query": {"match_all": {}}}, {}) is None
    assert svc.classify("missing*,other*", {}, {}) is None  # multi-target
    assert svc.classify("idx", "not-a-dict", {}) is None
    # fetch-phase keys post-process the response — still eligible
    assert svc.classify("idx", {"query": {"match_all": {}},
                                "_source": False}, {}) is not None


# ---- fairness ------------------------------------------------------------


def _pending(tenant):
    return PendingSearch(entry={"index": "i", "kwargs": {}}, tenant=tenant)


def test_starvation_heavy_tenant_cannot_block_light():
    """The starvation contract: with a heavy tenant holding 100 queued
    entries, a light tenant's 2 requests are claimed in the very next
    wave — weighted round-robin visits every non-empty tenant."""
    q = TenantQueues()
    for _ in range(100):
        q.push(_pending("heavy"))
    for _ in range(2):
        q.push(_pending("light"))
    wave = q.pop_wave(8)
    by_tenant = {}
    for ps in wave:
        by_tenant.setdefault(ps.tenant, 0)
        by_tenant[ps.tenant] += 1
    assert by_tenant.get("light", 0) >= 1, (
        f"light tenant starved out of the first wave: {by_tenant}")
    assert by_tenant["heavy"] >= 1  # fairness, not lockout of the heavy one


def test_weighted_budgets_respected():
    q = TenantQueues()
    q.set_weights(parse_tenant_weights("gold:3,bronze:1"))
    for _ in range(20):
        q.push(_pending("gold"))
        q.push(_pending("bronze"))
    wave = q.pop_wave(8)
    gold = sum(1 for ps in wave if ps.tenant == "gold")
    bronze = sum(1 for ps in wave if ps.tenant == "bronze")
    assert gold == 6 and bronze == 2  # 3:1 per round-robin visit


def test_parse_tenant_weights():
    assert parse_tenant_weights("a:4, b:1.5") == {"a": 4.0, "b": 1.5}
    assert parse_tenant_weights("") == {}
    assert parse_tenant_weights("bad") == {}


# ---- backpressure --------------------------------------------------------


class _GatedPool:
    """A 1-worker engine pool whose next submission can be held behind an
    event — deterministically freezes the wave pipeline mid-flight."""

    def __init__(self):
        self.pool = ThreadPoolExecutor(max_workers=1,
                                       thread_name_prefix="test-engine")
        self.gate = threading.Event()

    def block(self):
        self.gate.clear()
        self.pool.submit(self.gate.wait)

    def release(self):
        self.gate.set()

    def shutdown(self):
        self.gate.set()
        self.pool.shutdown(wait=True)


def _wait_until(pred, timeout=10.0):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if pred():
            return True
        time.sleep(0.005)
    return False


def test_queue_full_sheds_with_retry_after(served):
    engine, _idx, svc = served
    gated = _GatedPool()
    try:
        svc.bind_executor(gated.pool.submit)
        svc.set_queue_depth(1)
        gated.block()
        entry = svc.classify("idx", {"query": {"match_all": {}}}, {})
        f1 = svc.submit(entry, tenant="a")  # claimed into the frozen wave
        assert _wait_until(lambda: svc._tenants.depth == 0)
        f2 = svc.submit(dict(entry), tenant="a")  # queued (depth 1 = cap)
        with pytest.raises(ServingRejectedError) as ei:
            svc.submit(dict(entry), tenant="b")
        assert ei.value.status == 429
        assert ei.value.retry_after_s >= 1.0
        assert svc.stats()["shed"] == 1
        gated.release()
        f1.result(timeout=60)
        f2.result(timeout=60)
    finally:
        gated.release()
        svc.stop()
        gated.shutdown()


def test_breaker_trip_sheds_before_any_device_work(served):
    engine, _idx, svc = served
    entry = svc.classify("idx", {"query": {"match_all": {}}}, {})
    engine.breakers.children["in_flight_requests"].limit = 100  # < est_bytes
    try:
        with pytest.raises(CircuitBreakingError) as ei:
            svc.submit(entry)
        assert ei.value.status == 429
        assert ei.value.retry_after_s >= 1.0  # shed hint for _err_response
        st = svc.stats()
        assert st["shed"] == 1 and st["dispatched"] == 0
    finally:
        engine.breakers.children["in_flight_requests"].limit = (
            engine.breakers.total)


def test_deadline_expired_before_dispatch(served):
    """An entry whose queue wait exceeds its timeout resolves timed_out
    (empty partial result) WITHOUT a device dispatch, and its task is
    cancelled + unregistered through the task manager."""
    engine, _idx, svc = served
    gated = _GatedPool()
    try:
        svc.bind_executor(gated.pool.submit)
        gated.block()
        entry = svc.classify("idx", {"query": {"match_all": {}}}, {})
        f1 = svc.submit(entry, tenant="a")  # occupies the frozen pipeline
        assert _wait_until(lambda: svc.stats()["dispatched"] == 1)
        f2 = svc.submit(dict(entry), tenant="a", timeout_s=0.02)
        time.sleep(0.1)  # let the deadline lapse while still queued
        gated.release()
        res2 = f2.result(timeout=60)
        assert res2["timed_out"] is True
        assert res2["hits"]["hits"] == []
        f1.result(timeout=60)
        st = svc.stats()
        assert st["expired"] == 1
        assert st["dispatched"] == 1  # f2 never reached the device
        assert not [t for t in engine.tasks.list()
                    if t.action == svc.TASK_ACTION]
    finally:
        gated.release()
        svc.stop()
        gated.shutdown()


def test_cancel_queued_task_no_device_round_trip(served):
    """Task-manager cancel of a still-queued search removes it from the
    serving queue, resolves the caller with task_cancelled_exception, and
    reports cancelled: true — no dispatch ever happens for it."""
    engine, _idx, svc = served
    gated = _GatedPool()
    try:
        svc.bind_executor(gated.pool.submit)
        gated.block()
        entry = svc.classify("idx", {"query": {"match_all": {}}}, {})
        f1 = svc.submit(entry, tenant="a")
        assert _wait_until(lambda: svc.stats()["dispatched"] == 1)
        f2 = svc.submit(dict(entry), tenant="a")
        assert _wait_until(lambda: svc._tenants.depth == 1)
        queued = [t for t in engine.tasks.list()
                  if t.action == svc.TASK_ACTION]
        assert len(queued) == 2
        # cancel BOTH tasks: f1's is already claimed into the frozen wave
        # (its listener no-ops), f2's is still queued and must be removed
        for t in queued:
            got = engine.tasks.cancel(t.task_id)
            assert got and got[0].to_dict()["cancelled"] is True
        with pytest.raises(TaskCancelledException):
            f2.result(timeout=10)
        assert svc._tenants.depth == 0  # removed from the queue
        gated.release()
        f1.result(timeout=60)  # the in-flight wave still completes
        assert svc.stats()["dispatched"] == 1  # f2 never reached the device
        assert svc.stats()["cancelled"] >= 1
    finally:
        gated.release()
        svc.stop()
        gated.shutdown()


def test_stop_resolves_queued_entries(served):
    engine, _idx, svc = served
    gated = _GatedPool()
    svc.bind_executor(gated.pool.submit)
    gated.block()
    entry = svc.classify("idx", {"query": {"match_all": {}}}, {})
    f1 = svc.submit(entry)
    assert _wait_until(lambda: svc._tenants.depth == 0)
    f2 = svc.submit(dict(entry))
    gated.release()
    svc.stop()
    # both settle: completed in-flight, or rejected at shutdown
    for f in (f1, f2):
        try:
            f.result(timeout=10)
        except ServingRejectedError:
            pass
    gated.shutdown()
    svc.bind_executor(None)  # the gated pool is gone; use an owned one
    # restartable: a fresh submit after stop() runs normally
    f3 = svc.submit(svc.classify("idx", {"query": {"match_all": {}}}, {}))
    assert f3.result(timeout=60)["hits"]["total"]["value"] == 60


# ---- metrics -------------------------------------------------------------


def test_prometheus_serving_metrics(served):
    """The four satellite metrics land in the Prometheus exposition:
    queue_depth gauge, wave_occupancy + coalesce_wait_ms histograms, and
    shed_total counter."""
    from elasticsearch_tpu.telemetry import metrics

    engine, idx, svc = served
    idx.searcher  # merge tiers: occupancy records on term-lane waves
    entries = [svc.classify("idx", {"query": {"match": {"title": w}},
                                    "size": 3}, {})
               for w in ("alpha", "beta", "gamma")]
    futs = [svc.submit(e) for e in entries]
    wait(futs, timeout=120)
    [f.result() for f in futs]
    svc.set_queue_depth(1)
    gated = _GatedPool()
    try:
        svc.bind_executor(gated.pool.submit)
        gated.block()
        f1 = svc.submit(svc.classify("idx", {"query": {"match_all": {}}},
                                     {}))
        assert _wait_until(lambda: svc._tenants.depth == 0)
        f2 = svc.submit(svc.classify("idx", {"query": {"match_all": {}}},
                                     {}))
        with pytest.raises(ServingRejectedError):
            svc.submit(svc.classify("idx", {"query": {"match_all": {}}},
                                    {}))
        gated.release()
        f1.result(timeout=60)
        f2.result(timeout=60)
    finally:
        gated.release()
        svc.stop()
        gated.shutdown()
    text = metrics.prometheus_text()
    for name in ("es_serving_queue_depth", "es_serving_wave_occupancy",
                 "es_serving_coalesce_wait_ms", "es_serving_shed_total"):
        assert name in text, f"{name} missing from Prometheus exposition"
    st = svc.stats()
    assert st["term_packed"] >= 3
    assert st["wave"]["avg_term_occupancy"] is not None


# ---- REST e2e ------------------------------------------------------------


@pytest.fixture
def client_run(tmp_path):
    def _run(scenario, engine=None):
        from aiohttp.test_utils import TestClient, TestServer

        from elasticsearch_tpu.rest import make_app

        async def wrapper():
            app = make_app(engine=engine,
                           data_path=str(tmp_path / "restdata"))
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                return await scenario(client, app["engine"])
            finally:
                await client.close()

        loop = asyncio.new_event_loop()
        try:
            return loop.run_until_complete(wrapper())
        finally:
            loop.close()

    return _run


def test_rest_serving_end_to_end(client_run):
    """Enable coalescing via cluster settings; concurrent searches return
    parity responses, /_serving/stats and _nodes/stats expose the
    accounting, and a breaker trip surfaces as 429 + Retry-After."""

    async def scenario(c, engine):
        r = await c.put("/books", json={"mappings": {"properties": {
            "title": {"type": "text"}}}})
        assert r.status == 200
        for i in range(30):
            await c.put(f"/books/_doc/{i}",
                        json={"title": f"{WORDS[i % 7]} common"})
        await c.post("/books/_refresh")
        body = {"query": {"match": {"title": "common"}}, "size": 5}
        solo = await (await c.post("/books/_search", json=body)).json()
        r = await c.put("/_cluster/settings", json={
            "persistent": {"serving.enabled": True,
                           "serving.tenant.weights": "gold:4"}})
        assert r.status == 200
        rs = await asyncio.gather(*[
            c.post("/books/_search", json=body,
                   headers={"X-Opaque-Id": f"tenant-{i % 2}"})
            for i in range(12)])
        assert all(r.status == 200 for r in rs)
        for r in rs:
            got = await r.json()
            got.pop("took"), solo.pop("took", None)
            assert json.dumps(got, sort_keys=True) == json.dumps(
                solo, sort_keys=True)
        st = (await (await c.get("/_serving/stats")).json())["serving"]
        assert st["enabled"] is True and st["completed"] >= 12
        assert st["waves"] >= 1
        ns = await (await c.get("/_nodes/stats")).json()
        node = list(ns["nodes"].values())[0]
        assert node["serving"]["completed"] >= 12
        # backpressure: trip the admission breaker -> 429 + Retry-After
        engine.breakers.children["in_flight_requests"].limit = 1
        r = await c.post("/books/_search", json=body)
        assert r.status == 429
        assert int(r.headers["Retry-After"]) >= 1
        err = await r.json()
        assert err["error"]["type"] == "circuit_breaking_exception"
        engine.breakers.children["in_flight_requests"].limit = (
            engine.breakers.total)
        # msearch rides the same coalescing queue concurrently
        lines = []
        for w in ("alpha", "beta", "delta"):
            lines.append(json.dumps({"index": "books"}))
            lines.append(json.dumps(
                {"query": {"match": {"title": w}}, "size": 3}))
        r = await c.post("/_msearch", data="\n".join(lines) + "\n",
                         headers={"Content-Type": "application/x-ndjson"})
        assert r.status == 200
        resp = await r.json()
        assert [x["status"] for x in resp["responses"]] == [200] * 3

    client_run(scenario)


# ---- 512-way stress (slow) -----------------------------------------------


@pytest.mark.slow
def test_512_way_concurrency_parity(served):
    """512 closed-loop requests across 32 client threads and 8 tenants:
    every coalesced response byte-identical to solo execution, with the
    request count packed into far fewer device waves."""
    engine, idx, svc = served
    idx.searcher  # merged: the term lane carries the bulk of the traffic
    rng = np.random.default_rng(7)
    bodies = []
    for i in range(512):
        kind = i % 8
        if kind < 5:  # term-lane majority, varied shapes
            w = WORDS[int(rng.integers(0, 7))]
            bodies.append({"query": {"match": {"title": w}},
                           "size": int(rng.integers(1, 8))})
        elif kind == 5:
            bodies.append({"query": {"term": {"tag": WORDS[i % 3]}},
                           "size": 4})
        elif kind == 6:
            bodies.append({"query": {"match": {"title": "common"}},
                           "size": 5,
                           "aggs": {"t": {"terms": {"field": "tag"}}}})
        else:
            bodies.append({"query": {"match_all": {}}, "size": 3,
                           "from": i % 4})
    solo = [json.dumps(_solo(engine, b), sort_keys=True) for b in bodies]
    entries = [svc.classify("idx", b, {}) for b in bodies]
    assert all(e is not None for e in entries)
    results = [None] * 512
    lock = threading.Lock()
    it = iter(range(512))

    def client(tenant):
        while True:
            with lock:
                i = next(it, None)
            if i is None:
                return
            f = svc.submit(entries[i], tenant=tenant)
            results[i] = json.dumps(f.result(timeout=300), sort_keys=True)

    threads = [threading.Thread(target=client, args=(f"tenant-{t % 8}",))
               for t in range(32)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert all(r is not None for r in results)
    mismatches = [i for i in range(512) if results[i] != solo[i]]
    assert not mismatches, f"parity broke at {mismatches[:5]}"
    st = svc.stats()
    assert st["completed"] == 512
    # the whole point: far fewer device waves than requests
    assert st["waves"] < 512 / 4, f"no coalescing: {st['waves']} waves"
    assert st["term_packed"] > 0


# ---- PR 35: what a wave adds to the counters and the span tree ------------


def _wave_counters():
    from elasticsearch_tpu.telemetry import metrics

    c = metrics.snapshot()["counters"]
    return {k: v for k, v in c.items()
            if k.startswith(("es.serving.wave.", "es.span."))}


def test_a_wave_adds_its_members_rows_wait_and_stages(served):
    """A wave of n term-lane members adds 1 wave, n members and tier(n)
    rows; its four stages lie end to end inside its wall; and a member's
    own `rest.search` records the wave's stages under the solo path's names,
    an n-th of each."""
    from elasticsearch_tpu.ops.batched import BatchTermSearcher
    from elasticsearch_tpu.telemetry import TRACER, WAVE_STAGES

    engine, idx, svc = served
    assert idx.searcher is not None   # merges the tiers: one base, a term lane
    bodies = [{"query": {"match": {"title": w}}, "size": 5}
              for w in ("alpha", "beta gamma", "delta", "common", "epsilon")]
    n = len(bodies)
    entries = [svc.classify("idx", b, {}) for b in bodies]
    before = _wave_counters()
    # shipped from the service's start, at 0: every cell's readers find them
    assert before["es.serving.wave.count"] >= 0
    assert "es.span.engine.wave_fetch.ns" in before
    # admitted under the scheduler's own lock, so that it finds all five at
    # its first look and closes them into one wave
    with svc._cv:
        futs = [svc.submit(e) for e in entries]
    wait(futs, timeout=120)
    assert all(f.exception() is None for f in futs)
    after = _wave_counters()
    added = {k: after[k] - before.get(k, 0) for k in after}
    assert added["es.serving.wave.count"] == 1
    assert added["es.serving.wave.members"] == n
    assert added["es.serving.wave.padded_rows"] \
        == BatchTermSearcher.wave_q_tier(n) == 8
    assert added["es.serving.wave.wait_ns"] > 0
    rec = svc.flight_recorder()["waves"][-1]
    assert rec["size"] == n
    stage_ns = [added[f"es.span.{s}.ns"] for s in WAVE_STAGES]
    assert all(ns > 0 for ns in stage_ns)
    assert [added[f"es.span.{s}.count"] for s in WAVE_STAGES][2:] == [1, 1]
    # plan is split by its launch: one span before it and one behind it
    assert added["es.span.engine.wave_launch.count"] >= 1
    assert added["es.span.engine.wave_plan.count"] \
        == added["es.span.engine.wave_launch.count"] + 1
    seg = rec["segments_ms"]
    assert sum(stage_ns) / 1e6 <= seg["plan"] + seg["device"] + seg["finish"]
    # a member's own stages, recorded in its request's context
    with TRACER.span("rest.search"):
        svc.member_spans(futs[0])
    member = _wave_counters()
    moved = {k: member[k] - after.get(k, 0) for k in member}
    for stage in ("engine.queue", "engine.search", "engine.parse",
                  "engine.plan", "engine.dispatch", "engine.fetch",
                  "engine.collect", "rest.search"):
        assert moved[f"es.span.{stage}.count"] == 1, stage
    shares = sum(moved[f"es.span.engine.{s}.ns"]
                 for s in ("parse", "plan", "dispatch", "fetch", "collect"))
    assert 0 < shares <= sum(stage_ns) // n + 5
    assert shares <= moved["es.span.engine.search.ns"]
    assert moved["es.span.engine.queue.ns"] * n \
        <= added["es.serving.wave.wait_ns"] * n   # its own wait, not a share
    # a future that no wave answered records nothing
    from concurrent.futures import Future

    svc.member_spans(Future())


def test_a_dense_stream_holds_the_window_and_a_lone_request_does_not(served):
    """The idle-pipeline shortcut is for a sparse stream: a request whose
    predecessor came within the coalescing window waits for company, a lone
    one is dispatched at once, whatever came before."""
    engine, _idx, svc = served
    svc.set_max_wait("50ms")
    entry = lambda: svc.classify(  # noqa: E731
        "idx", {"query": {"match": {"title": "alpha"}}, "size": 5}, {})
    t0 = time.monotonic()
    svc.submit(entry()).result(timeout=60)          # the first ever: alone
    assert svc.stats()["waves"] == 1
    with svc._cv:                                    # two, a moment apart
        futs = [svc.submit(entry()), svc.submit(entry())]
    wait(futs, timeout=60)
    assert svc.stats()["waves"] == 2, "the pair rode one wave"
    time.sleep(0.12)                                 # the stream thins out
    t1 = time.monotonic()
    svc.submit(entry()).result(timeout=60)
    assert svc.stats()["waves"] == 3
    assert time.monotonic() - t1 < 0.05 + (t1 - t0), "a lone request waited"
