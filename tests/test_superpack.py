"""Tenant superpacks (PR 17): size-class bucketing, byte parity vs
per-index dispatch, O(size-classes) compiled-program count, per-tenant
cache-epoch scoping, and tenant isolation under injected fold faults."""

import asyncio
import os

import numpy as np
import pytest

from elasticsearch_tpu.common import faults
from elasticsearch_tpu.engine.engine import Engine
from elasticsearch_tpu.tenancy import size_class_of, superpack_enabled
from elasticsearch_tpu.tenancy.superpack import MIN_BLOCK_CLASS, MIN_DOC_CLASS

WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta"]


@pytest.fixture(autouse=True)
def _superpack_on(monkeypatch):
    monkeypatch.setenv("ES_TPU_SUPERPACK", "1")
    faults.clear()
    yield
    faults.clear()
    faults.configure_from_env()


@pytest.fixture
def engine(tmp_path):
    e = Engine(str(tmp_path / "data"))
    yield e
    e.close()


def _tenant(engine, name, n=6, seed=0):
    idx = engine.create_index(name, {"properties": {
        "body": {"type": "text"}}})
    for i in range(n):
        idx.index_doc(str(i), {
            "body": f"{WORDS[(i + seed) % 7]} "
                    f"{WORDS[(i + seed + 2) % 7]} common"})
    idx.refresh()
    return idx


def _entry(name, query=None, size=5, **kw):
    kwargs = {"query": query or {"match": {"body": "alpha common"}},
              "size": size, **kw}
    return {"index": name, "kwargs": kwargs, "expression": name}


def _run_wave(mgr, entries):
    """Drive the duck-typed wave-job protocol directly (the service's
    begin → completer fetch → finish sequence, single-threaded)."""
    job = mgr.search_wave_begin(entries)
    mgr.search_wave_fetch(job)
    return job, mgr.search_wave_finish(job)


# ---------------------------------------------------------------------------
# size classes + membership
# ---------------------------------------------------------------------------

def test_size_class_bucketing():
    assert size_class_of(1, 1) == (MIN_DOC_CLASS, MIN_BLOCK_CLASS)
    assert size_class_of(MIN_DOC_CLASS, MIN_BLOCK_CLASS) == (
        MIN_DOC_CLASS, MIN_BLOCK_CLASS)
    assert size_class_of(MIN_DOC_CLASS + 1, 1) == (
        2 * MIN_DOC_CLASS, MIN_BLOCK_CLASS)
    assert size_class_of(1000, 40) == (1024, 64)
    # classes are pow2 on both axes: two tenants in one class share one
    # device layout and one compiled program family
    n1, b1 = size_class_of(70, 3)
    n2, b2 = size_class_of(100, 5)
    assert (n1, b1) == (n2, b2)


def test_superpack_enabled_env_overrides(engine, monkeypatch):
    monkeypatch.setenv("ES_TPU_SUPERPACK", "0")
    assert not superpack_enabled(engine.settings)
    assert engine.superpacks_if_enabled() is None
    monkeypatch.setenv("ES_TPU_SUPERPACK", "1")
    assert superpack_enabled(engine.settings)
    monkeypatch.delenv("ES_TPU_SUPERPACK")
    assert not superpack_enabled(engine.settings)  # setting default False
    engine.settings.update({"persistent": {"superpack.enabled": True}})
    assert superpack_enabled(engine.settings)


def test_adopt_folds_lsm_tail_and_registers_lane(engine):
    idx = _tenant(engine, "ta")
    mgr = engine.superpacks
    assert mgr.adopt(idx)
    member = mgr.member_of("ta")
    assert member is not None and member.num_docs == 6
    # the fold major-merged the tail into a sealed base (the `_merge`
    # tenant contract): the member searcher IS the current base
    assert not idx._tails and member.ss is idx._searcher
    # idempotent while current
    assert mgr.adopt(idx)
    assert mgr.member_count() == 1


def test_oversize_tenant_not_adopted(engine, monkeypatch):
    engine.settings.update({"persistent": {"superpack.max_docs": 4}})
    idx = _tenant(engine, "big", n=9)
    assert not engine.superpacks.adopt(idx)
    assert engine.superpacks.member_of("big") is None


# ---------------------------------------------------------------------------
# byte parity vs per-index dispatch
# ---------------------------------------------------------------------------

def test_solo_row_byte_parity_vs_sharded_msearch(engine):
    from elasticsearch_tpu.parallel.sharded import msearch_sharded

    mgr = engine.superpacks
    tenants = {f"t{i}": _tenant(engine, f"t{i}", n=4 + i, seed=i)
               for i in range(4)}
    for idx in tenants.values():
        assert mgr.adopt(idx)
    queries = [[("alpha", 1.0), ("common", 1.0)],
               [("gamma", 2.0)],
               [("common", 1.0), ("zeta", 1.0), ("beta", 0.5)]]
    for name, idx in tenants.items():
        bv, bs, bi, bt = msearch_sharded(idx._searcher, "body", queries, k=5)
        sv, ss_, si, st = mgr.msearch(name, "body", queries, k=5)
        assert np.array_equal(bt, st)
        for q in range(len(queries)):
            nb = int(np.isfinite(bv[q]).sum())
            ns = int(np.isfinite(sv[q]).sum())
            assert nb == ns, (name, q)
            # BYTE parity: identical f32 bit patterns, identical docids
            assert np.array_equal(
                bv[q][:nb].view(np.uint32), sv[q][:nb].view(np.uint32)), \
                (name, q, bv[q][:nb], sv[q][:nb])
            assert np.array_equal(bi[q][:nb], si[q][:nb])


def test_wave_response_parity_and_job_accounting(engine):
    mgr = engine.superpacks
    tenants = {f"t{i}": _tenant(engine, f"t{i}", n=5 + i, seed=i)
               for i in range(5)}
    for idx in tenants.values():
        assert mgr.adopt(idx)
    entries, solo = [], []
    for name, idx in tenants.items():
        body = {"match": {"body": f"{WORDS[len(entries) % 7]} common"}}
        e = _entry(name, query=body, size=4)
        assert mgr.wave_claim(e), name
        entries.append(e)
        solo.append(idx.search(query=body, size=4))
    job, out = _run_wave(mgr, entries)
    assert job["index_names"] == list(tenants)
    assert job["meta"]["term_packed"] == len(entries)
    assert job["meta"]["transitions"]["dispatch"] == 1
    assert job["meta"]["transitions"]["fetch"] == 1
    assert job["meta"]["term_waves"]
    for resp, base in zip(out, solo):
        assert resp["hits"]["hits"] == base["hits"]["hits"]
        assert resp["hits"]["total"] == base["hits"]["total"]
        assert resp["hits"]["max_score"] == base["hits"]["max_score"]


def test_wave_claim_rejects_ineligible_entries(engine):
    mgr = engine.superpacks
    idx = _tenant(engine, "ta")
    assert mgr.adopt(idx)
    # non-term-disjunction query -> per-index path
    assert not mgr.wave_claim(_entry("ta", query={"range": {
        "body": {"gte": "a"}}}))
    # wave-unsupported feature -> per-index path
    assert not mgr.wave_claim(_entry("ta", aggs={"t": {"terms": {
        "field": "body"}}}))
    # unknown index
    assert not mgr.wave_claim(_entry("nope"))
    # a stale member (new writes) is NOT claimed: per-index serves the
    # fresh view while the background refold catches the lane up
    idx.index_doc("99", {"body": "late write"})
    assert not mgr.wave_claim(_entry("ta"))


def test_stale_lane_refolds_and_serves_new_docs(engine):
    mgr = engine.superpacks
    # n=5 keeps the refreshed tenant inside the same block size class,
    # so the refold reuses the lane and bumps its per-lane epoch
    idx = _tenant(engine, "ta", n=5)
    assert mgr.adopt(idx)
    old = mgr.member_of("ta")
    idx.index_doc("9", {"body": "alpha common fresh"})
    idx.refresh()
    assert not mgr.wave_claim(_entry("ta"))  # stale vs the new searcher
    assert mgr.refold("ta")
    member = mgr.member_of("ta")
    assert member.epoch == old.epoch + 1 and member.num_docs == 6
    e = _entry("ta", query={"match": {"body": "fresh"}})
    assert mgr.wave_claim(e)
    _job, out = _run_wave(mgr, [e])
    assert [h["_id"] for h in out[0]["hits"]["hits"]] == ["9"]


# ---------------------------------------------------------------------------
# O(size-classes) compiled programs (the tentpole contract)
# ---------------------------------------------------------------------------

def test_compiled_program_count_bounded_by_size_class(engine):
    mgr = engine.superpacks
    names = [f"t{i}" for i in range(12)]
    for i, name in enumerate(names):
        assert mgr.adopt(_tenant(engine, name, n=5 + (i % 2), seed=i))
    assert len(mgr.packs) == 1  # all land in one size class
    entries = []
    for name in names:
        e = _entry(name, query={"match": {"body": "common"}}, size=3)
        assert mgr.wave_claim(e)
        entries.append(e)
    _run_wave(mgr, entries)
    for name in names:
        mgr.msearch(name, "body", [[("common", 1.0)]], k=3)
    # 12 tenants, >= 13 dispatches — compiled programs stay bounded by
    # (size classes x shape tiers), NEVER by tenant count
    assert mgr.compiled_program_count() <= 4
    assert mgr.member_count() == 12


def test_lane_growth_preserves_existing_lanes(engine):
    """Folding past MIN_LANES grows the pack's lane capacity; every
    already-resident tenant must stay byte-identical through the growth
    (regression: the grown free-list range used to re-lease an occupied
    lane, silently overwriting an earlier tenant's postings)."""
    from elasticsearch_tpu.parallel.sharded import msearch_sharded

    mgr = engine.superpacks
    names = [f"g{i}" for i in range(11)]
    for i, name in enumerate(names):
        assert mgr.adopt(_tenant(engine, name, n=5 + (i % 2), seed=i))
    assert len(mgr.packs) == 1
    pack = next(iter(mgr.packs.values()))
    assert pack.capacity > 8  # growth actually happened
    lanes = [pack.lanes[n].lane for n in names]
    assert len(set(lanes)) == len(names)  # no lane ever re-leased
    queries = [[("common", 1.0)], [("alpha", 1.0), ("beta", 1.0)]]
    for name in names:
        ss = engine.indices[name]._searcher
        v_sp, _, i_sp, t_sp = mgr.msearch(name, "body", queries, k=5)
        v_px, _, i_px, t_px = msearch_sharded(ss, "body", queries, 5)
        kk = min(v_sp.shape[-1], v_px.shape[-1])
        assert np.array_equal(
            np.asarray(v_sp)[..., :kk].view(np.uint32),
            np.asarray(v_px)[..., :kk].view(np.uint32)), name
        assert np.array_equal(np.asarray(t_sp), np.asarray(t_px)), name


# ---------------------------------------------------------------------------
# per-tenant cache-epoch scoping (satellite 1)
# ---------------------------------------------------------------------------

def test_tenant_scoped_cache_epochs(engine, monkeypatch):
    """Two tenants in one superpack: A serving hot from the request
    cache, B refreshing. B's refold must invalidate ONLY B's entries —
    A's stay resident and keep hitting."""
    monkeypatch.setenv("ES_TPU_REQUEST_CACHE", "1")
    from elasticsearch_tpu.cache import request_cache
    from elasticsearch_tpu.telemetry import collect_profile_events

    rc = request_cache()
    mgr = engine.superpacks
    a = _tenant(engine, "ta", n=5, seed=0)
    b = _tenant(engine, "tb", n=6, seed=3)
    assert mgr.adopt(a) and mgr.adopt(b)
    pack = next(iter(mgr.packs.values()))
    lane_a = mgr.member_of("ta").lane
    lane_b = mgr.member_of("tb").lane

    def cache_events(entries):
        with collect_profile_events() as events:
            _run_wave(mgr, entries)
        return [e for e in events
                if e["kind"] == "cache" and e["scope"] == "superpack_gather"]

    def claimed(name):
        e = _entry(name, query={"match": {"body": "common"}})
        assert mgr.wave_claim(e), name
        return e

    def lane_keys(lane):
        return [k for k in rc.lru._map
                if k[0] == (pack.cache_token, lane)]

    ev = cache_events([claimed("ta"), claimed("tb")])
    assert sum(e["misses"] for e in ev) == 2  # both cold
    assert lane_keys(lane_a) and lane_keys(lane_b)
    ev = cache_events([claimed("ta"), claimed("tb")])
    assert sum(e["hits"] for e in ev) == 2  # both hot now
    a_keys = lane_keys(lane_a)

    # B refreshes + refolds: ONLY B's lane entries drop
    b.index_doc("99", {"body": "common newcomer"})
    b.refresh()
    assert mgr.refold("tb")
    assert lane_keys(lane_a) == a_keys  # neighbor untouched (hot)
    assert not lane_keys(lane_b)  # refreshed tenant fully dropped

    ev = cache_events([claimed("ta"), claimed("tb")])
    by_hits = sum(e["hits"] for e in ev)
    by_miss = sum(e["misses"] for e in ev)
    assert by_hits == 1 and by_miss == 1  # A still hot, B re-misses
    # ...and B's re-computed row reflects the new doc
    e = _entry("tb", query={"match": {"body": "newcomer"}})
    assert mgr.wave_claim(e)
    _job, out = _run_wave(mgr, [e])
    assert [h["_id"] for h in out[0]["hits"]["hits"]] == ["99"]


def test_delete_index_evicts_lane_and_cache(engine, monkeypatch):
    monkeypatch.setenv("ES_TPU_REQUEST_CACHE", "1")
    from elasticsearch_tpu.cache import request_cache

    rc = request_cache()
    mgr = engine.superpacks
    _tenant(engine, "ta")
    idx_b = _tenant(engine, "tb")
    assert mgr.adopt(engine.get_index("ta")) and mgr.adopt(idx_b)
    pack = next(iter(mgr.packs.values()))
    lane_b = mgr.member_of("tb").lane
    e = _entry("tb")
    assert mgr.wave_claim(e)
    _run_wave(mgr, [e])
    assert [k for k in rc.lru._map if k[0] == (pack.cache_token, lane_b)]
    engine.delete_index("tb")
    assert mgr.member_of("tb") is None
    assert lane_b in pack.free
    assert not pack.host["live"][lane_b].any()
    assert not [k for k in rc.lru._map
                if k[0] == (pack.cache_token, lane_b)]
    # the survivor still serves
    e = _entry("ta")
    assert mgr.wave_claim(e)
    _job, out = _run_wave(mgr, [e])
    assert out[0]["hits"]["total"]["value"] >= 1


# ---------------------------------------------------------------------------
# tenant isolation under injected fold faults (satellite 3)
# ---------------------------------------------------------------------------

def _lane_snapshot(pack):
    return {k: np.asarray(v).copy() for k, v in pack.host.items()}


def _assert_lanes_equal(pack, snap, exclude=()):
    for k, arr in pack.host.items():
        cur, old = np.asarray(arr), snap[k]
        for lane in range(min(cur.shape[0], old.shape[0])):
            if lane in exclude:
                continue
            assert np.array_equal(cur[lane], old[lane]), (k, lane)


def test_refresh_build_fault_during_fold_isolates_neighbors(engine):
    mgr = engine.superpacks
    tenants = {f"t{i}": _tenant(engine, f"t{i}", n=4 + i, seed=i)
               for i in range(4)}
    for idx in tenants.values():
        assert mgr.adopt(idx)
    pack = next(iter(mgr.packs.values()))
    snap = _lane_snapshot(pack)
    before = {n: mgr.msearch(n, "body", [[("common", 1.0)]], k=4)
              for n in tenants if n != "t1"}

    tenants["t1"].index_doc("9", {"body": "common churn"})
    tenants["t1"].refresh()
    faults.configure("refresh.build:error=error,match=superpack_fold")
    with pytest.raises(faults.InjectedFault):
        mgr.refold("t1")
    faults.clear()
    # every neighbor lane is BYTE-identical, host and results alike
    lane_1 = mgr.member_of("t1").lane
    _assert_lanes_equal(pack, snap, exclude=(lane_1,))
    for n, (bv, _bs, bi, bt) in before.items():
        sv, _ss, si, st = mgr.msearch(n, "body", [[("common", 1.0)]], k=4)
        assert np.array_equal(bv.view(np.uint32), sv.view(np.uint32))
        assert np.array_equal(bi, si) and np.array_equal(bt, st)
    # the faulted tenant's lane is stale but its index still serves solo
    assert not mgr.wave_claim(_entry("t1"))
    assert tenants["t1"].search(query={"match": {"body": "churn"}},
                                size=3)["hits"]["total"]["value"] == 1


def test_superpack_fold_fault_leaves_old_lane_then_retry_lands(engine):
    mgr = engine.superpacks
    a = _tenant(engine, "ta", n=5, seed=0)
    b = _tenant(engine, "tb", n=5, seed=2)
    assert mgr.adopt(a) and mgr.adopt(b)
    pack = next(iter(mgr.packs.values()))
    snap = _lane_snapshot(pack)
    old_b = mgr.member_of("tb")

    b.index_doc("9", {"body": "common churn"})
    b.refresh()
    faults.configure("superpack.fold:once=1,match=tb")
    with pytest.raises(faults.InjectedFault):
        mgr.refold("tb")
    # atomic install: the injected fault fired BEFORE any handle swap —
    # every lane (including B's old one) is byte-identical
    _assert_lanes_equal(pack, snap)
    assert mgr.member_of("tb") is old_b
    assert pack.fold_failures == 1
    assert mgr.stats()["fold_failures"] == 1
    # retry (the schedule_fold path re-arms on the next claim): lands
    assert mgr.refold("tb")
    member = mgr.member_of("tb")
    assert member is not old_b and member.num_docs == 6
    e = _entry("tb", query={"match": {"body": "churn"}})
    assert mgr.wave_claim(e)
    _job, out = _run_wave(mgr, [e])
    assert [h["_id"] for h in out[0]["hits"]["hits"]] == ["9"]


# ---------------------------------------------------------------------------
# serving-service integration
# ---------------------------------------------------------------------------

def test_serving_wave_mixes_tenants_with_parity(engine):
    mgr = engine.superpacks
    tenants = {f"t{i}": _tenant(engine, f"t{i}", n=4 + i, seed=i)
               for i in range(5)}
    for idx in tenants.values():
        assert mgr.adopt(idx)
    engine.settings.update({"persistent": {"serving.enabled": True}})
    svc = engine.serving
    try:
        body = {"query": {"match": {"body": "alpha common"}}, "size": 4}
        solo = {n: idx.search(query=body["query"], size=4)
                for n, idx in tenants.items()}
        futs = [(n, svc.submit(svc.classify(n, dict(body), {})))
                for n in tenants for _ in range(2)]
        for n, f in futs:
            res = f.result(timeout=20)
            assert res["hits"]["hits"] == solo[n]["hits"]["hits"]
            assert res["hits"]["total"] == solo[n]["hits"]["total"]
        assert svc.counters["term_packed"] >= len(futs) // 2
        # flight records name the member tenants, not "_superpack"
        recs = svc.flight_recorder()["waves"]
        waves = [r for r in recs if r.get("indices")]
        assert waves and all("_superpack" not in r["indices"]
                             for r in waves)
        named = {n for r in waves for n in r["indices"]}
        assert named & set(tenants)
    finally:
        svc.stop()


def test_serving_schedules_background_fold_for_stale_member(engine):
    mgr = engine.superpacks
    idx = _tenant(engine, "ta", n=4)
    assert mgr.adopt(idx)
    engine.settings.update({"persistent": {"serving.enabled": True}})
    svc = engine.serving
    try:
        idx.index_doc("9", {"body": "alpha common fresh"})
        idx.refresh()
        old = mgr.member_of("ta")
        body = {"query": {"match": {"body": "fresh"}}, "size": 3}
        # the stale claim serves per-index (correct fresh results) and
        # schedules the refold as the `_merge` internal tenant
        res = svc.submit(svc.classify("ta", dict(body), {})).result(
            timeout=20)
        assert [h["_id"] for h in res["hits"]["hits"]] == ["9"]
        deadline = 50
        # a refold into another size class releases the stale lane before it
        # folds the new one: no lane for a moment, seen from this thread
        while mgr.member_of("ta") in (old, None) and deadline:
            import time as _t

            _t.sleep(0.1)
            deadline -= 1
        assert mgr.member_of("ta") is not old, "background refold missed"
        assert mgr.member_of("ta").num_docs == 5
        e = _entry("ta", query=body["query"], size=3)
        assert mgr.wave_claim(e)
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# stats / REST / gauges (satellite 2)
# ---------------------------------------------------------------------------

def test_manager_stats_and_gauges(engine):
    from elasticsearch_tpu.telemetry import metrics

    mgr = engine.superpacks
    for i in range(3):
        assert mgr.adopt(_tenant(engine, f"t{i}", n=5 + (i % 2), seed=i))
    st = mgr.stats()
    assert st["members"] == 3 and st["size_classes"] == 1
    assert st["hbm_bytes"] > 0
    assert st["hbm_bytes_per_tenant"] == st["hbm_bytes"] // 3
    assert 0.0 < st["padded_waste_pct"] <= 100.0
    cls = next(iter(st["classes"].values()))
    assert cls["members"] == 3 and cls["hbm_bytes_per_tenant"] > 0
    snap = metrics.snapshot()["gauges"]
    assert snap["es.superpack.members"] == 3
    assert snap["es.superpack.waste_pct"] == st["padded_waste_pct"]
    ms = mgr.member_stats("t0")
    assert ms and ms["size_class"] and ms["hbm_bytes_per_tenant"] > 0
    assert mgr.member_stats("absent") is None
    # superpack padded HBM rides the node-wide waste accounting (PR 5)
    from elasticsearch_tpu.monitoring.device import padded_waste_bytes

    assert padded_waste_bytes(engine) >= st["padded_waste_bytes"]


def test_rest_superpack_sections():
    async def go():
        from aiohttp.test_utils import TestClient, TestServer

        from elasticsearch_tpu.rest.app import make_app

        client = TestClient(TestServer(make_app()))
        await client.start_server()
        try:
            engine = client.server.app["engine"]
            for i in range(2):
                await client.put(f"/sp{i}", json={"mappings": {
                    "properties": {"body": {"type": "text"}}}})
                await client.put(f"/sp{i}/_doc/1?refresh=true",
                                 json={"body": "alpha common"})
            mgr = engine.superpacks
            for i in range(2):
                assert mgr.adopt(engine.get_index(f"sp{i}"))
            stats = await (await client.get("/_nodes/stats")).json()
            sp = stats["nodes"]["node-0"]["superpack"]
            assert sp["members"] == 2 and sp["size_classes"] == 1
            assert sp["hbm_bytes_per_tenant"] > 0
            assert "padded_waste_pct" in sp
            cat = await (await client.get(
                "/_cat/indices?format=json")).json()
            rows = {r["index"]: r for r in cat}
            assert rows["sp0"]["superpack"]["size_class"] == \
                rows["sp1"]["superpack"]["size_class"]
            assert rows["sp0"]["superpack"]["hbm_bytes_per_tenant"] > 0
            prom = await (await client.get(
                "/_prometheus/metrics")).text()
            assert "es_superpack_members 2" in prom
            assert "es_superpack_waste_pct" in prom
        finally:
            await client.close()

    asyncio.run(go())


def test_faults_registry_has_superpack_fold():
    assert "superpack.fold" in faults.FAULT_POINTS
