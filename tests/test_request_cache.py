"""Shard request cache (elasticsearch_tpu/cache/): LRU + keys + epoch
invalidation across the scatter/gather path, plus the round-5 satellite
regressions (solver memoization, health status propagation, transport
handler unregistration).

The hard contract under test: a cached result is BYTE-IDENTICAL to the
uncached execution of the same request, and no stale entry is reachable
after any write becomes visible (refresh/delete/merge)."""

import json
import threading

import numpy as np
import pytest

from elasticsearch_tpu.cache import (
    ShardRequestCache,
    SizedLru,
    canonical_key,
    request_cache,
)
from elasticsearch_tpu.index.mappings import Mappings


@pytest.fixture(autouse=True)
def _cache_on(monkeypatch):
    """The shuffled-order gate exports ES_TPU_REQUEST_CACHE=0 so the cache
    can never mask an execution bug elsewhere; THESE tests exercise the
    cache itself and must see it enabled. The session _env_hermetic
    fixture restores the gate's env afterwards."""
    monkeypatch.delenv("ES_TPU_REQUEST_CACHE", raising=False)
    # one cache object a process: tests/test_param_pack.py switches it off
    # through REST, and may have run before on this worker
    request_cache().set_enabled(True)


# ---------------------------------------------------------------------------
# LRU core
# ---------------------------------------------------------------------------

def test_lru_eviction_under_size_limit():
    removed = []
    lru = SizedLru(100, removal_listener=lambda k, v, r: removed.append((k, r)))
    assert lru.put("a", "A", 40)
    assert lru.put("b", "B", 40)
    assert lru.get("a") == "A"  # touches a: b is now LRU
    assert lru.put("c", "C", 40)  # evicts b
    assert lru.get("b") is None
    assert lru.get("a") == "A"
    assert lru.get("c") == "C"
    st = lru.stats()
    assert st["evictions"] == 1
    assert st["memory_size_in_bytes"] == 80
    assert ("b", "evicted") in removed
    # oversized entry: counted, dropped, nothing evicted for it
    assert not lru.put("huge", "H", 101)
    assert lru.stats()["too_large"] == 1
    assert lru.get("a") == "A"


def test_lru_stats_internally_consistent_concurrent():
    lru = SizedLru(1 << 16)
    errs = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(300):
                k = int(rng.integers(0, 40))
                if rng.random() < 0.5:
                    lru.get(k)
                else:
                    lru.put(k, k, 64)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    st = lru.stats()
    assert st["hit_count"] + st["miss_count"] == st["lookups"]
    assert st["memory_size_in_bytes"] == st["entry_count"] * 64


def test_lru_breaker_trip_rejects_entry():
    from elasticsearch_tpu.common.breaker import CircuitBreakerService

    brk = CircuitBreakerService(total_bytes=1 << 20,
                               limits={"request": "1kb", "total": "100%"})

    def account(delta):
        if delta >= 0:
            brk.add_estimate("request", delta, "request_cache")
        else:
            brk.release("request", -delta)

    lru = SizedLru(1 << 20, account=account)
    assert lru.put("ok", "x", 512)
    assert brk.children["request"].used == 512
    # second entry would exceed the 1kb request breaker: tripped + dropped
    assert not lru.put("big", "y", 900)
    assert lru.stats()["breaker_trips"] == 1
    assert brk.children["request"].trip_count == 1
    assert lru.get("big") is None
    # eviction releases the charged bytes back to the breaker
    lru.invalidate("ok")
    assert brk.children["request"].used == 0


def test_request_cache_breaker_trip_on_oversized_entry():
    from elasticsearch_tpu.common.breaker import CircuitBreakerService

    brk = CircuitBreakerService(total_bytes=1 << 20,
                               limits={"request": "256b", "total": "100%"})
    rc = ShardRequestCache(max_bytes=1 << 16)
    rc.bind_breaker(lambda d: brk.add_estimate("request", d, "rc")
                    if d >= 0 else brk.release("request", -d))
    assert not rc.put((1, 0), (0, 0), "k", "value", 512)
    assert brk.children["request"].trip_count == 1
    assert rc.get((1, 0), (0, 0), "k") is None


# ---------------------------------------------------------------------------
# canonical keys
# ---------------------------------------------------------------------------

def test_canonical_key_normalizes_equivalent_requests():
    a = {"bool": {"must": {"term": {"f": "x"}}, "boost": 1.0}}
    b = {"bool": {"boost": 1, "must": [{"term": {"f": "x"}}]}}
    assert canonical_key(a) == canonical_key(b)
    # key order inside leaf objects is irrelevant
    c = {"range": {"n": {"gte": 1, "lte": 5}}}
    d = {"range": {"n": {"lte": 5, "gte": 1}}}
    assert canonical_key(c) == canonical_key(d)
    # different semantics -> different keys
    assert canonical_key({"term": {"f": "x"}}) != canonical_key(
        {"term": {"f": "y"}})
    # clause ORDER is preserved (float addition is order-sensitive)
    e = {"bool": {"should": [{"term": {"f": "x"}}, {"term": {"f": "y"}}]}}
    f = {"bool": {"should": [{"term": {"f": "y"}}, {"term": {"f": "x"}}]}}
    assert canonical_key(e) != canonical_key(f)


# ---------------------------------------------------------------------------
# executor: cached vs uncached parity + per-query msearch entries
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def shard_searcher():
    from elasticsearch_tpu.index.pack import PackBuilder
    from elasticsearch_tpu.query import ShardSearcher

    m = Mappings({"properties": {"body": {"type": "text"}}})
    b = PackBuilder(m)
    rng = np.random.default_rng(7)
    for _ in range(64):
        b.add_document(m.parse_document(
            {"body": " ".join(f"t{t}" for t in rng.integers(0, 30, 12))}))
    return ShardSearcher(b.build(), mappings=m)


def test_executor_search_cached_parity(shard_searcher):
    s = shard_searcher
    rc = request_cache()
    q = {"bool": {"should": [{"term": {"body": "t3"}},
                             {"term": {"body": "t7"}}]}}
    st0 = rc.stats()
    r1 = s.search(q, size=8)
    r2 = s.search(q, size=8)
    st1 = rc.stats()
    assert st1["hit_count"] - st0["hit_count"] == 1
    assert st1["miss_count"] - st0["miss_count"] == 1
    # scores AND docids byte-identical
    assert r1.scores.tobytes() == r2.scores.tobytes()
    assert r1.doc_ids.tobytes() == r2.doc_ids.tobytes()
    assert (r1.total, r1.max_score) == (r2.total, r2.max_score)
    # the served copy is defensive: mutating it must not poison the cache
    r2.scores[:] = -1
    r3 = s.search(q, size=8)
    assert r3.scores.tobytes() == r1.scores.tobytes()


def test_executor_msearch_per_query_entries(shard_searcher):
    s = shard_searcher
    rc = request_cache()
    qs = [[("t1", 1.0), ("t4", 1.0)], [("t2", 1.0)], [("t9", 2.0)]]
    cold = s.msearch("body", qs, 5)
    st0 = rc.stats()
    # a partially-overlapping batch: only the new query is dispatched
    qs2 = [qs[1], [("t11", 1.0)], qs[0]]
    mixed = s.msearch("body", qs2, 5)
    st1 = rc.stats()
    assert st1["hit_count"] - st0["hit_count"] == 2
    assert st1["miss_count"] - st0["miss_count"] == 1
    assert np.array_equal(mixed[0][0], cold[0][1])  # scores of qs[1]
    assert np.array_equal(mixed[1][0], cold[1][1])  # docids of qs[1]
    assert np.array_equal(mixed[0][2], cold[0][0])  # scores of qs[0]
    assert np.array_equal(mixed[1][2], cold[1][0])  # docids of qs[0]
    assert mixed[2][0] == cold[2][1] and mixed[2][2] == cold[2][0]
    warm = s.msearch("body", qs, 5)
    for a, b in zip(cold, warm):
        assert np.array_equal(a, b)


def test_executor_msearch_epoch_bump_forces_recompute(shard_searcher):
    s = shard_searcher
    rc = request_cache()
    qs = [[("t5", 1.0)]]
    a = s.msearch("body", qs, 5)
    s.bump_epoch()
    st0 = rc.stats()
    b = s.msearch("body", qs, 5)
    st1 = rc.stats()
    assert st1["miss_count"] - st0["miss_count"] == 1
    for x, y in zip(a, b):
        assert np.array_equal(x, y)  # pack unchanged: same bytes, fresh entry


def test_cache_disabled_by_env(shard_searcher, monkeypatch):
    monkeypatch.setenv("ES_TPU_REQUEST_CACHE", "0")
    rc = request_cache()
    st0 = rc.stats()
    shard_searcher.search({"term": {"body": "t2"}}, size=3)
    shard_searcher.search({"term": {"body": "t2"}}, size=3)
    assert rc.stats()["lookups"] == st0["lookups"]


# ---------------------------------------------------------------------------
# sharded msearch: per-shard entries, partial warmth, parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def stacked():
    from elasticsearch_tpu.parallel.sharded import StackedSearcher
    from elasticsearch_tpu.parallel.stacked import build_stacked_pack

    rng = np.random.default_rng(13)
    m = Mappings({"properties": {"body": {"type": "text"}}})
    docs = [(f"d{i}", {"body": " ".join(
        f"t{t}" for t in rng.integers(0, 50, 9))}) for i in range(240)]
    sp = build_stacked_pack(docs, m, num_shards=4)
    return StackedSearcher(sp, mesh=None)


def test_msearch_sharded_per_shard_cache_and_parity(stacked):
    from elasticsearch_tpu.parallel.sharded import (
        _msearch_sharded_exact, msearch_sharded,
    )

    ss = stacked
    rc = request_cache()
    rng = np.random.default_rng(5)
    qs = [[(f"t{t}", 1.0) for t in rng.integers(0, 50, 3)] for _ in range(6)]
    S = ss.sp.S
    st0 = rc.stats()
    a = msearch_sharded(ss, "body", qs, 5)
    warm = msearch_sharded(ss, "body", qs, 5)
    st1 = rc.stats()
    # pass 1: every (query, shard) missed; pass 2: every one hit
    assert st1["miss_count"] - st0["miss_count"] == len(qs) * S
    assert st1["hit_count"] - st0["hit_count"] == len(qs) * S
    exact = _msearch_sharded_exact(ss, "body", qs, 5)
    for got in (a, warm):
        for x, y in zip(got, exact):
            assert np.array_equal(np.asarray(x), np.asarray(y))


def test_msearch_sharded_partial_shard_invalidation(stacked):
    from elasticsearch_tpu.parallel.sharded import msearch_sharded

    ss = stacked
    rc = request_cache()
    rng = np.random.default_rng(8)
    qs = [[(f"t{t}", 1.0) for t in rng.integers(0, 50, 3)] for _ in range(5)]
    S = ss.sp.S
    base = msearch_sharded(ss, "body", qs, 5)
    # one shard's epoch bumps (in-place mutation of that shard only):
    # the other shards stay warm — a partially-warm msearch re-uses their
    # cached rows and only the cold shard's entries are refilled
    ss.bump_epoch(shard=1)
    st0 = rc.stats()
    again = msearch_sharded(ss, "body", qs, 5)
    st1 = rc.stats()
    assert st1["hit_count"] - st0["hit_count"] == len(qs) * (S - 1)
    assert st1["miss_count"] - st0["miss_count"] == len(qs)
    for x, y in zip(base, again):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_stacked_search_whole_searcher_scope_invalidated_by_any_shard(stacked):
    ss = stacked
    rc = request_cache()
    q = {"term": {"body": "t12"}}
    r1 = ss.search(q, size=6)
    st0 = rc.stats()
    r2 = ss.search(q, size=6)
    assert rc.stats()["hit_count"] - st0["hit_count"] == 1
    ss.bump_epoch(shard=2)  # merged results depend on EVERY shard
    st1 = rc.stats()
    r3 = ss.search(q, size=6)
    assert rc.stats()["miss_count"] - st1["miss_count"] == 1
    for a, b in ((r1, r2), (r1, r3)):
        assert a.scores.tobytes() == b.scores.tobytes()
        assert np.array_equal(a.doc_ids, b.doc_ids)
        assert np.array_equal(a.doc_shards, b.doc_shards)


# ---------------------------------------------------------------------------
# engine: invalidation after refresh / delete, end-to-end parity
# ---------------------------------------------------------------------------

def test_engine_refresh_and_delete_invalidate():
    from elasticsearch_tpu.engine.engine import Engine

    eng = Engine()
    rc = eng.request_cache
    idx = eng.create_index(
        "rc_idx", mappings={"properties": {"body": {"type": "text"}}})
    for i in range(24):
        idx.index_doc(f"d{i}", {"body": f"alpha t{i % 5} beta"})
    idx.refresh()
    q = {"match": {"body": "alpha t3"}}
    r1 = idx.search(query=q, size=6)
    st0 = rc.stats()
    r2 = idx.search(query=q, size=6)
    st1 = rc.stats()
    assert st1["hit_count"] > st0["hit_count"]
    assert json.dumps(r1, sort_keys=True, default=str) == \
        json.dumps(r2, sort_keys=True, default=str)
    # a write + refresh between identical queries forces a miss and the
    # result reflects the mutation
    idx.delete_doc("d3")
    idx.refresh()
    st2 = rc.stats()
    r3 = idx.search(query=q, size=6)
    st3 = rc.stats()
    assert st3["miss_count"] > st2["miss_count"]
    assert st3["hit_count"] == st2["hit_count"]
    ids = [h["_id"] for h in r3["hits"]["hits"]]
    assert "d3" not in ids
    assert r3["hits"]["total"]["value"] == \
        r1["hits"]["total"]["value"] - 1
    eng.delete_index("rc_idx")


def test_track_total_hits_shares_one_cache_entry():
    """`track_total_hits` formats the response and is no part of what was
    asked of the device: true and false are one entry, and each response
    is built by its own value."""
    from elasticsearch_tpu.engine.engine import Engine

    eng = Engine()
    rc = eng.request_cache
    idx = eng.create_index(
        "rc_tth", mappings={"properties": {"body": {"type": "text"}}})
    for i in range(24):
        idx.index_doc(f"d{i}", {"body": f"alpha t{i % 5} beta"})
    idx.refresh()
    tiers = len(idx.tier_searchers())  # each tier keeps its own entry
    q = {"match": {"body": "alpha t3"}}
    st0 = rc.stats()
    counted = idx.search(query=q, size=6, track_total_hits=True)
    st1 = rc.stats()
    uncounted = idx.search(query=q, size=6, track_total_hits=False)
    st2 = rc.stats()
    assert (st1["miss_count"] - st0["miss_count"],
            st1["hit_count"] - st0["hit_count"]) == (tiers, 0)
    assert (st2["miss_count"] - st1["miss_count"],
            st2["hit_count"] - st1["hit_count"]) == (0, tiers)
    assert counted["hits"]["total"] == {"value": 24, "relation": "eq"}
    assert "total" not in uncounted["hits"]
    assert counted["hits"]["hits"] == uncounted["hits"]["hits"]
    eng.delete_index("rc_tth")


def test_engine_dynamic_cache_settings():
    from elasticsearch_tpu.engine.engine import Engine

    eng = Engine()
    rc = eng.request_cache
    eng.settings.update(
        {"transient": {"indices.requests.cache.enable": False}})
    assert not rc.enabled
    eng.settings.update(
        {"transient": {"indices.requests.cache.size": "1mb"}})
    assert rc.lru.max_bytes == 1 << 20
    eng.settings.update(
        {"transient": {"indices.requests.cache.enable": None,
                       "indices.requests.cache.size": None}})
    assert rc.enabled


# ---------------------------------------------------------------------------
# round-5 satellite regressions
# ---------------------------------------------------------------------------

def test_desired_balance_compute_memoized(monkeypatch):
    from dataclasses import replace

    from elasticsearch_tpu.cluster import allocation, desired_balance
    from elasticsearch_tpu.cluster.state import ClusterState

    calls = {"n": 0}
    orig = desired_balance._compute_uncached

    def counting(state):
        calls["n"] += 1
        return orig(state)

    monkeypatch.setattr(desired_balance, "_compute_uncached", counting)
    nodes = {f"n{i}": {"roles": ["data"], "attributes": {}}
             for i in range(3)}
    st = ClusterState(term=1, version=1, nodes=nodes)
    st = allocation.create_index_state(
        st, "i0", {}, {"number_of_shards": 2, "number_of_replicas": 1})
    desired_balance._memo.clear()  # start cold for deterministic counting
    before = calls["n"]
    d1 = desired_balance.compute(st)
    d2 = desired_balance.compute(st)
    assert calls["n"] == before + 1  # second solve served from the memo
    assert d1 == d2
    # solver-irrelevant changes (version bump, engine ops) share the solve
    st_v = replace(st, version=st.version + 7)
    desired_balance.compute(st_v)
    assert calls["n"] == before + 1
    # a returned dict is a fresh copy: caller mutation can't poison the memo
    next(iter(d1.values())).append("poison")
    assert desired_balance.compute(st) == d2
    # routing-relevant change re-solves
    st2 = st.with_node("n9", {"roles": ["data"], "attributes": {}})
    desired_balance.compute(st2)
    assert calls["n"] == before + 2


def test_cluster_health_propagates_replica_status():
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from elasticsearch_tpu.cluster.http import make_cluster_app
    from elasticsearch_tpu.cluster.state import ClusterState

    class _Coord:
        leader = "n1"

    class _Node:
        node_id = "n1"
        coordinator = _Coord()
        state = ClusterState(
            term=1, version=1, nodes={"n1": {}},
            indices={"i": {"settings": {}}},
            routing={"i": {"0": [{"node": "n1", "primary": True,
                                  "state": "STARTED",
                                  "allocation_id": "a1"}]}})

    class _Server:
        node = _Node()

    class _Replica:
        failed = None
        engine_port = 1
        payload = (408, json.dumps({"status": "red", "timed_out": True,
                                    "active_shards": 0}).encode(), "")

        async def _call(self, method, path, body, ct):
            return self.payload

        async def handle(self, request):  # catch-all route stub
            from aiohttp import web

            return web.json_response({})

    async def scenario():
        replica = _Replica()
        app = make_cluster_app(_Server(), replica=replica)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            # wait_for_status timeout: 408 + timed_out propagate
            r = await client.get("/_cluster/health?wait_for_status=red")
            assert r.status == 408
            body = await r.json()
            assert body["timed_out"] is True and body["status"] == "red"
            # invalid replica body: falls back to routing-table health, 200
            replica.payload = (200, b"not json at all", "")
            r2 = await client.get("/_cluster/health")
            assert r2.status == 200
            body2 = await r2.json()
            assert body2["status"] == "green"
            replica.payload = (200, json.dumps(["not", "a", "dict"]).encode(), "")
            r3 = await client.get("/_cluster/health")
            assert r3.status == 200
            assert (await r3.json())["status"] == "green"
        finally:
            await client.close()

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(scenario())
    finally:
        loop.close()


def test_transport_unregister_and_replace():
    from elasticsearch_tpu.transport.base import TransportService

    class _Net:
        def attach(self, node_id, svc):
            pass

    svc = TransportService("a", _Net())
    h1 = lambda req, frm, ch: None
    h2 = lambda req, frm, ch: None
    svc.register_async_handler("engine:dump", h1)
    with pytest.raises(ValueError):
        svc.register_async_handler("engine:dump", h1)
    # register-or-replace is the supported rebinding path
    svc.replace_async_handler("engine:dump", h2)
    assert svc._async_handlers["engine:dump"] is h2
    # a stopped component must not tear down its successor's binding
    assert not svc.unregister_handler("engine:dump", h1)
    assert svc._async_handlers["engine:dump"] is h2
    assert svc.unregister_handler("engine:dump", h2)
    assert "engine:dump" not in svc._async_handlers
    assert not svc.unregister_handler("engine:dump")
    # sync handlers unregister through the same API
    svc.register_handler("sync:op", lambda req, frm: {})
    with pytest.raises(ValueError):
        svc.replace_async_handler("sync:op", h1)
    assert svc.unregister_handler("sync:op")
