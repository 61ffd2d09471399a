"""PR 10: GSPMD (pjit) sharding of the pack — partition-rule table,
byte/rank parity of pjit vs shard_map vs single-device on the 1x8 CPU
mesh across bool/knn/impact/aggs/serving-wave plans, the on-device
all-gather top-k merge, replica groups, and the collective cost model.
"""

from __future__ import annotations

import numpy as np
import pytest

from elasticsearch_tpu.index.mappings import Mappings
from elasticsearch_tpu.parallel.sharded import (
    StackedSearcher,
    _merge_shard_rows,
    _msearch_exact_partials,
    global_merge_rows,
    make_mesh,
    msearch_sharded,
    msearch_wave,
)
from elasticsearch_tpu.parallel.spmd import (
    PACK_PARTITION_RULES,
    leaf_paths,
    match_partition_rules,
    merge_topk_rows,
    spmd_mode,
)
from elasticsearch_tpu.parallel.stacked import build_stacked_pack


def _corpus(n=640, seed=3):
    rng = np.random.default_rng(seed)
    words = [f"w{i}" for i in range(60)]
    docs = []
    for i in range(n):
        body = " ".join(rng.choice(words, size=int(rng.integers(4, 12))))
        if rng.random() < 0.03:
            body += " rareterm"
        docs.append((f"doc-{i}", {
            "body": body,
            "status": str(rng.choice(["a", "b", "c"])),
            "bytes": int(rng.integers(1, 1000)),
            "vec": [float(x) for x in rng.normal(size=8)],
        }))
    return docs


_MAPPING = {
    "properties": {
        "body": {"type": "text"},
        "status": {"type": "keyword"},
        "bytes": {"type": "long"},
        "vec": {"type": "dense_vector", "dims": 8,
                "similarity": "dot_product"},
    }
}


@pytest.fixture(scope="module")
def sp():
    return build_stacked_pack(_corpus(), Mappings(_MAPPING), num_shards=4)


def _searcher(sp, mode, monkeypatch, mesh=True):
    monkeypatch.setenv("ES_TPU_SPMD", mode)
    return StackedSearcher(sp, mesh=make_mesh(4) if mesh else None)


def _queries(n=12, seed=11):
    rng = np.random.default_rng(seed)
    return [
        [(f"w{int(t)}", 1.0) for t in sorted(set(rng.integers(0, 60, 3)))]
        for _ in range(n)
    ]


# ---------------------------------------------------------------------------
# partition-rule table
# ---------------------------------------------------------------------------

def test_every_pack_leaf_matches_exactly_one_rule(sp):
    """The full-featured pack (postings, impact codes, dense tier,
    docvalues, vectors) flattens into leaves that each match EXACTLY one
    rule — the exhaustiveness contract of the table."""
    import re

    from elasticsearch_tpu.parallel.sharded import _stacked_host_tree

    host = _stacked_host_tree(sp)
    paths = leaf_paths(host)
    assert len(paths) >= 10  # postings, norms, dv, vec at minimum
    for name, leaf in paths:
        if np.ndim(leaf) == 0 or int(np.prod(np.shape(leaf))) == 1:
            continue
        hits = [rx for rx, _ in PACK_PARTITION_RULES if re.search(rx, name)]
        assert len(hits) == 1, (name, hits)
        assert np.shape(leaf)[0] == sp.S, (
            f"rule-sharded leaf [{name}] must carry the shard axis first")
    # the matcher itself runs clean over the real tree
    specs = leaf_paths(match_partition_rules(host))
    assert len(specs) == len(paths)


def test_unmatched_leaf_is_a_hard_error():
    with pytest.raises(ValueError, match="no partition rule"):
        match_partition_rules({"mystery_component": np.zeros((4, 8))})


def test_overlapping_rules_are_a_hard_error():
    from jax.sharding import PartitionSpec as P

    rules = [(r"^post", P("shards")), (r"docids$", P("shards"))]
    with pytest.raises(ValueError, match="matched 2"):
        match_partition_rules({"post_docids": np.zeros((4, 8))}, rules)


def test_scalars_replicate():
    from jax.sharding import PartitionSpec as P

    specs = match_partition_rules({"live": np.zeros((4, 8)),
                                   "nested": {"x": np.float32(1.0)}})
    assert specs["nested"]["x"] == P()
    assert specs["live"] == P("shards")


# ---------------------------------------------------------------------------
# byte/rank parity: pjit vs shard_map vs single-device
# ---------------------------------------------------------------------------

def _same_result(a, b, what):
    assert a.doc_shards.tolist() == b.doc_shards.tolist(), what
    assert a.doc_ids.tolist() == b.doc_ids.tolist(), what
    np.testing.assert_allclose(a.scores, b.scores, rtol=1e-6, err_msg=what)
    assert a.total == b.total, what
    assert a.aggregations == b.aggregations, what


def test_three_way_parity_bool_knn_aggs(sp, monkeypatch):
    monkeypatch.setenv("ES_TPU_REQUEST_CACHE", "0")
    pj = _searcher(sp, "pjit", monkeypatch)
    sm = _searcher(sp, "shardmap", monkeypatch)
    sd = _searcher(sp, "pjit", monkeypatch, mesh=False)
    assert (pj._exec, sm._exec, sd._exec) == ("pjit", "shardmap", "vmap")

    q = {"bool": {"should": [{"term": {"body": "rareterm"}},
                             {"term": {"body": "w1"}},
                             {"term": {"body": "w2"}}]}}
    aggs = {"by_status": {"terms": {"field": "status"},
                          "aggs": {"b": {"sum": {"field": "bytes"}}}}}
    knn = {"knn": {"field": "vec", "query_vector": [0.1] * 8, "k": 5,
                   "num_candidates": 20}}
    for req in (dict(query=q, size=7),
                dict(query=q, size=5, aggs=aggs),
                dict(query=knn, size=5),
                dict(query=None, size=0, aggs=aggs)):
        r_pj = pj.search(**req)
        _same_result(r_pj, sm.search(**req), ("shardmap", req))
        _same_result(r_pj, sd.search(**req), ("single", req))


def test_msearch_parity_and_device_merge(sp, monkeypatch):
    """The pjit msearch is ONE program including the merge; its rows are
    byte-identical to the shard_map partials + host lexsort merge."""
    monkeypatch.setenv("ES_TPU_REQUEST_CACHE", "0")
    pj = _searcher(sp, "pjit", monkeypatch)
    sm = _searcher(sp, "shardmap", monkeypatch)
    sd = _searcher(sp, "pjit", monkeypatch, mesh=False)
    queries = _queries()
    ref = msearch_sharded(pj, "body", queries, k=5)
    for other in (sm, sd):
        v, s_, d_, t_ = msearch_sharded(other, "body", queries, k=5)
        np.testing.assert_array_equal(ref[0], v)
        fin = np.isfinite(ref[0])
        assert (ref[1] == s_)[fin].all()
        assert (ref[2] == d_)[fin].all()
        assert (ref[3] == t_).all()


def test_impact_arm_rides_the_merged_program(sp, monkeypatch):
    """With the impact tier serving, the pjit path scores the sparse tail
    from the quantized codes inside the same merged program — parity vs
    the shard_map impact partials + host merge."""
    monkeypatch.setenv("ES_TPU_REQUEST_CACHE", "0")
    monkeypatch.setenv("ES_TPU_REQUEST_CACHE", "0")
    monkeypatch.setenv("ES_TPU_IMPACT", "1")
    if sp.impact_meta is None:
        pytest.skip("corpus built without an impact tier")
    pj = _searcher(sp, "pjit", monkeypatch)
    sm = _searcher(sp, "shardmap", monkeypatch)
    assert "impact_codes" in pj.dev
    from elasticsearch_tpu.telemetry import collect_profile_events

    queries = _queries(8, seed=23)
    with collect_profile_events() as events:
        ref = msearch_sharded(pj, "body", queries, k=5)
    names = [e.get("kernel") for e in events if e.get("kind") == "kernel"]
    assert "sharded.allgather_topk" in names
    tiers = [e.get("tier") for e in events if e.get("kind") == "tier"]
    assert "impact" in tiers
    v, s_, d_, t_ = msearch_sharded(sm, "body", queries, k=5)
    np.testing.assert_array_equal(ref[0], v)
    fin = np.isfinite(ref[0])
    assert (ref[1] == s_)[fin].all() and (ref[2] == d_)[fin].all()


def test_serving_wave_parity(sp, monkeypatch):
    """msearch_wave (the serving term lane) pads to the compiled batch
    tier and rides the merged pjit program — rows byte-identical to the
    shard_map wave."""
    monkeypatch.setenv("ES_TPU_REQUEST_CACHE", "0")
    pj = _searcher(sp, "pjit", monkeypatch)
    sm = _searcher(sp, "shardmap", monkeypatch)
    queries = _queries(5, seed=29)  # pads to the 8-wide tier
    (v_a, s_a, d_a, t_a), tier_a = msearch_wave(pj, "body", queries, k=5)
    (v_b, s_b, d_b, t_b), tier_b = msearch_wave(sm, "body", queries, k=5)
    assert tier_a == tier_b == 8
    np.testing.assert_array_equal(v_a, v_b)
    fin = np.isfinite(v_a)
    assert (s_a == s_b)[fin].all() and (d_a == d_b)[fin].all()
    assert (t_a == t_b).all()


def _odd_block_counts(sp):
    """Every shard's posting blocks, and the padded count, are not a
    multiple of 8: the count at which a v5e lays a shard's [1, m, 128]
    leaf out (1,128) and a squeeze of it would relay the whole leaf."""
    assert sp.nb_max % 8 and all(p.num_blocks % 8 for p in sp.shards)


def test_sorted_and_collapse_parity(sp, monkeypatch):
    """The mesh's sorted, collapse and rescore-gather (`scores_at`)
    programs answer as the shard_map oracle and as one device's `vmap`."""
    monkeypatch.setenv("ES_TPU_REQUEST_CACHE", "0")
    _odd_block_counts(sp)
    pj = _searcher(sp, "pjit", monkeypatch)
    sm = _searcher(sp, "shardmap", monkeypatch)
    sd = _searcher(sp, "pjit", monkeypatch, mesh=False)
    from elasticsearch_tpu.query.sort import parse_sort

    q = {"term": {"body": "w1"}}
    sort = parse_sort([{"bytes": "desc"}])
    h_pj = pj.search_sorted(q, sort, size=6)
    for other in (sm, sd):
        h = other.search_sorted(q, sort, size=6)
        assert h_pj[0] == h[0] and h_pj[1] == h[1]
    c_pj = pj.search_collapse(q, "status", size=3)
    for other in (sm, sd):
        c = other.search_collapse(q, "status", size=3)
        assert c_pj.doc_ids.tolist() == c.doc_ids.tolist()
        assert c_pj.collapse_keys == c.collapse_keys
        assert c_pj.total == c.total
        np.testing.assert_array_max_ulp(c_pj.scores, c.scores, maxulp=1)
    m = {"match": {"body": "w1 w7 rareterm"}}
    hits = sd.search(m, size=20)
    s_pj, ok_pj = pj.scores_at(m, hits.doc_shards, hits.doc_ids)
    s_sd, ok_sd = sd.scores_at(m, hits.doc_shards, hits.doc_ids)
    assert ok_pj.all() and ok_pj.tolist() == ok_sd.tolist()
    np.testing.assert_array_max_ulp(s_pj, s_sd, maxulp=1)


@pytest.mark.parametrize("arm", ["impact", "exact"])
def test_match_on_the_mesh_answers_as_one_device(sp, arm, monkeypatch):
    """The solo `match` program (`_compiled`) on the 4-device mesh, at
    block counts that are not multiples of 8, against the one-device
    `vmap` path: same shards and ids, same totals, scores within 1 ulp."""
    monkeypatch.setenv("ES_TPU_REQUEST_CACHE", "0")
    monkeypatch.setenv("ES_TPU_IMPACT", "1" if arm == "impact" else "0")
    _odd_block_counts(sp)
    pj = _searcher(sp, "pjit", monkeypatch)
    sd = _searcher(sp, "pjit", monkeypatch, mesh=False)
    for text in ("w1", "w3 w9 w41", "rareterm w2 w5 w8 w13 w21"):
        req = dict(query={"match": {"body": text}}, size=7)
        a, b = pj.search(**req), sd.search(**req)
        assert a.doc_shards.tolist() == b.doc_shards.tolist(), text
        assert a.doc_ids.tolist() == b.doc_ids.tolist(), text
        assert a.total == b.total and a.total > 0, text
        np.testing.assert_array_max_ulp(a.scores, b.scores, maxulp=1)
    # the programs that answered are the arm's (the mode is in their key)
    assert f"'{arm}'" in repr(list(pj._cache)), list(pj._cache)


# ---------------------------------------------------------------------------
# the on-device merge itself
# ---------------------------------------------------------------------------

def test_device_merge_matches_host_lexsort(sp, monkeypatch):
    """sharded.global_merge == _merge_shard_rows byte-for-byte, including
    score ties (flat top_k index order == the host lexsort order given
    each shard row's internal (score desc, doc asc) order)."""
    monkeypatch.setenv("ES_TPU_REQUEST_CACHE", "0")
    sd = _searcher(sp, "pjit", monkeypatch, mesh=False)
    v, i, t = _msearch_exact_partials(sd, "body", _queries(6, seed=41), k=4)
    hv, hs, hi, ht = _merge_shard_rows(v, i, t)
    dv, ds, di, dt = global_merge_rows(sd, v, i, t)
    np.testing.assert_array_equal(hv, dv)
    np.testing.assert_array_equal(hs, ds)
    np.testing.assert_array_equal(hi, di)
    np.testing.assert_array_equal(ht, dt)


def test_merge_tie_break_order():
    """Synthetic ties: equal scores resolve (shard asc, doc asc)."""
    v = np.full((3, 1, 2), 1.0, np.float32)
    i = np.array([[[5, 9]], [[2, 7]], [[0, 1]]], np.int64)
    t = np.ones((3, 1), np.int64)
    import jax

    mv, ms, mi, mt = jax.device_get(merge_topk_rows(
        np.asarray(v), np.asarray(i), np.asarray(t)))
    assert ms[0].tolist() == [0, 0]  # shard 0 wins both tied slots
    assert mi[0].tolist() == [5, 9]
    assert mt[0] == 3
    hv, hs, hi, ht = _merge_shard_rows(v, i, t)
    np.testing.assert_array_equal(hs, ms)
    np.testing.assert_array_equal(hi, mi)


# ---------------------------------------------------------------------------
# replica groups
# ---------------------------------------------------------------------------

def test_replica_mesh_parity(sp, monkeypatch):
    """ES_TPU_REPLICAS=2 on 8 devices -> a (4, 2) mesh; the pack
    replicates across the second axis and results stay byte-identical."""
    monkeypatch.setenv("ES_TPU_REQUEST_CACHE", "0")
    monkeypatch.setenv("ES_TPU_SPMD", "pjit")
    monkeypatch.setenv("ES_TPU_REPLICAS", "2")
    mesh = make_mesh(4)
    assert mesh is not None and mesh.axis_names == ("shards", "replicas")
    assert mesh.devices.shape == (4, 2)
    rep = StackedSearcher(sp, mesh=mesh)
    monkeypatch.delenv("ES_TPU_REPLICAS")
    sd = _searcher(sp, "pjit", monkeypatch, mesh=False)
    queries = _queries(9, seed=31)
    a = msearch_sharded(rep, "body", queries, k=5)
    b = msearch_sharded(sd, "body", queries, k=5)
    np.testing.assert_array_equal(a[0], b[0])
    fin = np.isfinite(a[0])
    assert (a[1] == b[1])[fin].all() and (a[2] == b[2])[fin].all()
    r = rep.search({"term": {"body": "w1"}}, size=5)
    s = sd.search({"term": {"body": "w1"}}, size=5)
    _same_result(r, s, "replica search")


# ---------------------------------------------------------------------------
# collective cost model
# ---------------------------------------------------------------------------

def test_allgather_cost_model_hand_computed():
    from elasticsearch_tpu.monitoring.costmodel import (
        allgather_merge_cost, ici_peak, kernel_cost, utilization,
    )

    s, q, k = 8, 256, 10
    c = allgather_merge_cost(s, q, k)
    rows = s * q * k
    assert c["ici_bytes"] == rows * 12  # f32 score + i64 id per row
    assert c["flops"] == 2.0 * rows
    assert c["bytes"] == rows * 12 + q * k * 16
    # the one-program entry = shard scan + merge, tier-aware
    full = kernel_cost("sharded.allgather_topk",
                       dict(tier="exact", shards=s, queries=q, k=k,
                            num_docs=8 * 1024, rows=q * 4))
    assert full is not None and full["ici_bytes"] == c["ici_bytes"]
    assert full["bytes"] > c["bytes"]  # scan traffic rides on top
    util = utilization("sharded.global_merge",
                       dict(shards=s, queries=q, k=k), 0.01)
    assert util is not None and util["ici_util"] == pytest.approx(
        c["ici_bytes"] / 0.01 / ici_peak())


def test_ici_peak_env_override(monkeypatch):
    from elasticsearch_tpu.monitoring import costmodel

    monkeypatch.setenv("ES_TPU_PEAK_ICI", "123e9")
    assert costmodel.ici_peak() == 123e9


def test_time_kernel_records_ici_utilization(sp, monkeypatch):
    monkeypatch.setenv("ES_TPU_REQUEST_CACHE", "0")
    from elasticsearch_tpu.telemetry import collect_profile_events, metrics

    sd = _searcher(sp, "pjit", monkeypatch, mesh=False)
    v, i, t = _msearch_exact_partials(sd, "body", _queries(4, seed=43), k=3)
    with collect_profile_events() as events:
        global_merge_rows(sd, v, i, t)
    ks = [e for e in events if e.get("kernel") == "sharded.global_merge"]
    assert ks and "ici_util" in ks[0] and ks[0]["ici_bytes"] > 0
    snap = metrics.snapshot()
    assert "es.kernel.sharded.global_merge.ici_pct" in snap["histograms"]


# ---------------------------------------------------------------------------
# PR 11: the fused Pallas arm inside the ONE compiled SPMD program
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fsp():
    """Dense-tier stacked pack with synthetic cross-shard score ties:
    every body is repeated on 4 consecutive docs, and round-robin shard
    routing lands the copies on DIFFERENT shards — bit-identical scores
    that must resolve (score desc, shard asc, doc asc) through the
    merged on-device top-k."""
    rng = np.random.default_rng(7)
    zipf = 1.0 / np.arange(1, 65)
    zipf /= zipf.sum()
    docs = []
    for i in range(300):
        ln = max(3, int(rng.poisson(9)))
        body = " ".join(f"t{int(t)}" for t in rng.choice(64, size=ln,
                                                         p=zipf))
        for r in range(4):
            docs.append((f"d{i}-{r}", {"body": body}))
    return build_stacked_pack(
        docs, Mappings({"properties": {"body": {"type": "text"}}}),
        num_shards=4, dense_min_df=32)


def _fused_queries(n=12, seed=3):
    rng = np.random.default_rng(seed)
    return [
        [(f"t{int(t)}", 1.0) for t in sorted(set(rng.integers(0, 64, 3)))]
        for _ in range(n)
    ]


def test_fused_arm_rides_one_program_with_ties(fsp, monkeypatch):
    """The tentpole: the fused Pallas pipeline runs INSIDE the one
    compiled pjit program (embedded shard_map region + in-program
    all-gather merge, `sharded.fused_allgather_topk`) — byte parity vs
    the shard_map oracle's host merge, rank parity vs single-device,
    including the synthetic 4-way cross-shard score ties."""
    monkeypatch.setenv("ES_TPU_REQUEST_CACHE", "0")
    monkeypatch.setenv("ES_TPU_FUSED", "force")
    from elasticsearch_tpu.parallel.sharded import _fused_sharded_for
    from elasticsearch_tpu.telemetry import collect_profile_events

    pj = _searcher(fsp, "pjit", monkeypatch)
    sm = _searcher(fsp, "shardmap", monkeypatch)
    sd = _searcher(fsp, "pjit", monkeypatch, mesh=False)
    fs = _fused_sharded_for(pj)
    assert fs is not None and fs.usable(5), "fused arm must engage"
    queries = _fused_queries()
    with collect_profile_events() as events:
        ref = msearch_sharded(pj, "body", queries, k=5)
    names = [e.get("kernel") for e in events if e.get("kind") == "kernel"]
    assert "sharded.fused_allgather_topk" in names, names
    ks = [e for e in events
          if e.get("kernel") == "sharded.fused_allgather_topk"]
    assert "mfu" in ks[0] and "ici_util" in ks[0] and ks[0]["ici_bytes"] > 0
    assert "fused" in [e.get("tier") for e in events
                       if e.get("kind") == "tier"]
    # the top rows really are cross-shard ties (score-identical copies)
    assert (ref[0][:, 0] == ref[0][:, 1]).any(), "tie corpus lost its ties"
    # byte parity vs the shard_map oracle (fused partials + host merge)
    v, s_, d_, t_ = msearch_sharded(sm, "body", queries, k=5)
    np.testing.assert_array_equal(ref[0], v)
    fin = np.isfinite(ref[0])
    assert (ref[1] == s_)[fin].all() and (ref[2] == d_)[fin].all()
    assert (ref[3] == t_).all()
    # rank parity vs single-device (vmap batches the pipeline; fp
    # summation order may differ at the ulp level — same contract as
    # tests/test_fused.test_fused_msearch_sharded_parity)
    v2, s2, d2, t2 = msearch_sharded(sd, "body", queries, k=5)
    assert (ref[3] == t2).all()
    np.testing.assert_allclose(ref[0], v2, rtol=1e-6)
    for q in range(len(queries)):
        for pos in range(int(fin[q].sum())):
            if (ref[2][q][pos], ref[1][q][pos]) != (d2[q][pos], s2[q][pos]):
                a, b = float(ref[0][q][pos]), float(v2[q][pos])
                assert abs(a - b) <= 1e-5 * max(abs(b), 1.0), (q, pos)


def test_two_level_selection_inside_pjit_program(monkeypatch):
    """The per-shard selection of the compiled `search` program runs in two
    levels INSIDE the pjit program's embedded shard_map region, on a pack
    whose shards have more blocks than hits are asked for — parity vs the
    same program built with plain `lax.top_k` over each shard's whole row."""
    import jax
    import jax.numpy as jnp

    from elasticsearch_tpu.ops.scoring import SELECT_BLOCK
    from elasticsearch_tpu.parallel import sharded

    def plain(scores, match, live, k):
        n = live.shape[0]
        ok = match[:n] & live
        v, i = jax.lax.top_k(jnp.where(ok, scores[:n], -jnp.inf), k)
        return v, i, jnp.sum(ok, dtype=jnp.int32)

    monkeypatch.setenv("ES_TPU_REQUEST_CACHE", "0")
    big = build_stacked_pack(_corpus(n=4600), Mappings(_MAPPING),
                             num_shards=4)
    assert big.n_max > 6 * SELECT_BLOCK
    q = {"bool": {"should": [{"term": {"body": "w1"}},
                             {"term": {"body": "w2"}},
                             {"term": {"body": "rareterm"}}]}}
    r_select = _searcher(big, "pjit", monkeypatch).search(query=q, size=6)
    monkeypatch.setattr(sharded, "top_k_with_total", plain)
    r_plain = _searcher(big, "pjit", monkeypatch).search(query=q, size=6)
    assert len(r_select.doc_ids) == 6 and r_select.total > 6
    _same_result(r_select, r_plain, "two-levels-in-pjit")


# ---------------------------------------------------------------------------
# PR 11: request cache keys at wave scope on the merged route
# ---------------------------------------------------------------------------

def test_request_cache_keeps_merged_route_engaged(sp, monkeypatch):
    """With the cache ON, a pjit msearch stores post-merge rows at wave
    scope: cold queries ride the one-program route (previously an
    enabled cache silently forced the partials + host-merge path), warm
    queries are served with NO device work, and any shard's epoch bump
    invalidates."""
    from elasticsearch_tpu.cache import request_cache
    from elasticsearch_tpu.telemetry import collect_profile_events

    monkeypatch.delenv("ES_TPU_REQUEST_CACHE", raising=False)
    request_cache().lru.clear()
    pj = _searcher(sp, "pjit", monkeypatch)
    queries = _queries(6, seed=51)
    with collect_profile_events() as ev1:
        cold = msearch_sharded(pj, "body", queries, k=5)
    names = [e.get("kernel") for e in ev1 if e.get("kind") == "kernel"]
    assert "sharded.allgather_topk" in names, names
    with collect_profile_events() as ev2:
        warm = msearch_sharded(pj, "body", queries, k=5)
    assert not [e for e in ev2 if e.get("kind") == "kernel"], (
        "warm wave must not touch the device")
    hits = [e for e in ev2 if e.get("kind") == "cache"
            and e.get("scope") == "msearch_merged"]
    assert hits and hits[0]["hits"] == len(queries)
    for a, b in zip(cold, warm):
        np.testing.assert_array_equal(a, b)
    # partially-warm: one new query re-dispatches ONLY the cold subset
    mixed = queries + _queries(1, seed=77)
    with collect_profile_events() as ev3:
        out = msearch_sharded(pj, "body", mixed, k=5)
    hits3 = [e for e in ev3 if e.get("kind") == "cache"
             and e.get("scope") == "msearch_merged"]
    assert hits3[0]["hits"] == len(queries) and hits3[0]["misses"] == 1
    for a, b in zip(cold, out):
        np.testing.assert_array_equal(a, b[: len(queries)]
                                      if a.ndim else b[: len(queries)])
    # one shard's mutation invalidates the wave-scope rows
    pj.bump_epoch(shard=1)
    with collect_profile_events() as ev4:
        again = msearch_sharded(pj, "body", queries, k=5)
    assert "sharded.allgather_topk" in [
        e.get("kernel") for e in ev4 if e.get("kind") == "kernel"]
    for a, b in zip(cold, again):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# PR 11: host-transition counter — one dispatch + one fetch per wave
# ---------------------------------------------------------------------------

def test_wave_host_transitions(tmp_path, monkeypatch):
    """The serving-wave contract: every lane's programs launch in ONE
    dispatch phase and the whole wave resolves with ONE combined fetch
    (`serving.wave_program`) — asserted on the job meta AND the
    transition profile events, for a pure term wave and a mixed
    term+generic wave."""
    monkeypatch.setenv("ES_TPU_REQUEST_CACHE", "0")
    from elasticsearch_tpu.engine.engine import Engine
    from elasticsearch_tpu.telemetry import collect_profile_events

    e = Engine(str(tmp_path / "data"))
    try:
        idx = e.create_index("w", {"properties": {
            "body": {"type": "text"}, "tag": {"type": "keyword"}}})
        for i in range(48):
            idx.index_doc(str(i), {
                "body": f"t{i % 7} t{(i + 1) % 7} common",
                "tag": f"g{i % 3}"})
        idx.refresh()
        _ = idx.searcher
        term_entries = [dict(query={"match": {"body": "t1"}}, size=5),
                        dict(query={"match": {"body": "t2 t3"}}, size=4),
                        dict(query={"match": {"body": "common"}}, size=3)]
        solo = [idx.search(**dict(en)) for en in term_entries]
        for entries in (term_entries,
                        term_entries + [dict(query=None, size=0, aggs={
                            "g": {"terms": {"field": "tag"}}})]):
            idx.search_wave([dict(en) for en in entries])  # compile-warm
            with collect_profile_events() as events:
                job = idx.search_wave_begin([dict(en) for en in entries])
                idx.search_wave_fetch(job)
                out = idx.search_wave_finish(job)
            assert all(isinstance(r, dict) for r in out), out
            tr = job["meta"]["transitions"]
            assert tr["dispatch"] <= 1 and tr["fetch"] <= 1, tr
            kinds = [ev.get("transition") for ev in events
                     if ev.get("kind") == "transition"]
            assert kinds.count("dispatch") <= 1, kinds
            assert kinds.count("fetch") <= 1, kinds
            ks = [ev.get("kernel") for ev in events
                  if ev.get("kind") == "kernel"]
            assert "serving.wave_program" in ks, ks
            # wave == solo (the serving parity contract)
            for en, resp in zip(term_entries, out):
                assert resp["hits"]["hits"] == \
                    idx.search(**dict(en))["hits"]["hits"]
        assert solo  # solo responses computed before any wave ran
    finally:
        e.close()


# ---------------------------------------------------------------------------
# env routing
# ---------------------------------------------------------------------------

def test_spmd_mode_resolution(monkeypatch):
    monkeypatch.delenv("ES_TPU_SPMD", raising=False)
    assert spmd_mode() == "pjit"  # auto default
    monkeypatch.setenv("ES_TPU_SPMD", "shardmap")
    assert spmd_mode() == "shardmap"
    monkeypatch.setenv("ES_TPU_SPMD", "pjit")
    assert spmd_mode() == "pjit"


# ---------------------------------------------------------------------------
# PR 35: one bounded family of wave programs; a row does not depend on its wave
# ---------------------------------------------------------------------------

def _bench():
    """The benchmark's generator, reference and comparison (no JAX)."""
    import os
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(os.path.dirname(here), "benchmark")
    if path not in sys.path:
        sys.path.insert(0, path)
    from benchlib import compare, corpus, reference, stats

    return compare, corpus, reference, stats


_WAVE_DOCS, _WAVE_POOL, _WAVE_SEED = 2048, 128, 3500000023
_WAVE_CORPUS = {"generator": "zipf_text", "vocab": 3000, "zipf_s": 1.0,
                "doc_len_mean": 56, "doc_len_sd": 25, "doc_len_min": 4}
# the source's query shapes (benchmark/configs/msmarco-passage-1shard.json)
_WAVE_QUERY = {"from": "documents", "terms_share": {
    "1": 1, "2": 4, "3": 11, "4": 16, "5": 17, "6": 15, "7": 12, "8": 9,
    "9": 6, "10": 4, "11": 2, "12": 3}}
_WAVE_LIMITS = {"total_wrong": 0, "rank_gap": 2e-4, "score_gap": 2e-4,
                "order_wrong": 0, "repeat_diff": 0}


def _wave_pack(dense_min_df):
    _cmp, gen, reference, _stats = _bench()
    c = gen.build_corpus(_WAVE_SEED, _WAVE_DOCS, _WAVE_CORPUS)
    pool = gen.build_pool(_WAVE_SEED, c, _WAVE_QUERY, _WAVE_POOL)
    docs = [(str(d), {"body": " ".join(
        f"t{t}" for t in c.tok[c.starts[d]:c.starts[d] + c.lens[d]])})
        for d in range(c.n_docs)]
    pack = build_stacked_pack(
        docs, Mappings({"properties": {"body": {"type": "text"}}}),
        num_shards=1, dense_min_df=dense_min_df)
    return pack, pool, reference.Reference(c.lens, c.tok, 1)


@pytest.fixture(scope="module")
def wave_corpus():
    """A seeded small corpus of the benchmark's own generator on one shard,
    with a dense tier (df >= 64), its pool of the source's query shapes and
    the plain reference over the same arrays."""
    return _wave_pack(64)


@pytest.fixture(scope="module")
def wave_corpus_sparse():
    """The same corpus with every term in the postings (no dense tier)."""
    return _wave_pack(10 ** 9)


def _wave_terms(pool, members):
    return [[(f"t{t}", 1.0) for t in pool[m]] for m in members]


_ARMS = {"exact": {}, "impact": {"ES_TPU_IMPACT": "1"},
         "fused": {"ES_TPU_FUSED": "force"}}


def _arm_searcher(pack, arm, monkeypatch):
    monkeypatch.setenv("ES_TPU_REQUEST_CACHE", "0")
    for key, value in _ARMS[arm].items():
        monkeypatch.setenv(key, value)
    ss = _searcher(pack, "pjit", monkeypatch, mesh=False)
    if arm == "impact":
        ss.refresh_impacts()
    return ss


@pytest.mark.parametrize("arm", list(_ARMS))
def test_a_row_does_not_depend_on_its_wave_and_is_the_references(
        wave_corpus, wave_corpus_sparse, arm, monkeypatch):
    """The same query alone, in a wave of 7 and in a wave of 64 with other
    companions: bit-identical rows; and held against the NumPy float64 BM25
    of `benchmark/benchlib/reference.py` by the benchmark's own comparison
    under the cells' limits (totals exact, order right, scores and ranks
    within 2e-4, no answer to a query differing from its first). The fused
    arm on the pack with a dense tier (its batch is one 512-row chunk
    whatever the wave); the exact and the impact arm on the pack without:
    their dense tier is one f32 matmul of the wave's width, and the CPU
    backend's matmul adds in an order that follows the width and the
    thread count (1 ulp between widths; PERF.md section 7)."""
    from elasticsearch_tpu.telemetry import collect_profile_events

    compare, _gen, _reference, stats = _bench()
    pack, pool, ref = wave_corpus if arm == "fused" else wave_corpus_sparse
    ss = _arm_searcher(pack, arm, monkeypatch)
    rng = np.random.default_rng(_WAVE_SEED)
    probes = [int(q) for q in rng.choice(_WAVE_POOL, size=6, replace=False)]
    # one of the longest queries is always probed, as the benchmark's sample
    probes.append(max(range(_WAVE_POOL), key=lambda q: len(pool[q])))
    answers = []
    with collect_profile_events() as events:
        for q in probes:
            others = [m for m in range(_WAVE_POOL) if m != q]
            waves = ([q],
                     [int(m) for m in rng.choice(others, 3, False)] + [q]
                     + [int(m) for m in rng.choice(others, 3, False)],
                     [int(m) for m in rng.choice(others, 63, False)] + [q])
            rows = []
            for members in waves:
                (v, s, d, t), tier = msearch_wave(
                    ss, "body", _wave_terms(pool, members), k=10)
                assert tier == 1 << (len(members) - 1).bit_length()
                at = members.index(q)
                rows.append((v[at], d[at], int(t[at])))
                n = int(np.isfinite(v[at]).sum())
                answers.append(stats.Request(
                    q, 0.0, 0.0, 200, ids=[int(x) for x in d[at][:n]],
                    scores=[float(x) for x in v[at][:n]],
                    total={"value": int(t[at]), "relation": "eq"}))
            for v, d, t in rows[1:]:
                fin = np.isfinite(rows[0][0])
                np.testing.assert_array_equal(v, rows[0][0])
                assert (d[fin] == rows[0][1][fin]).all() and t == rows[0][2]
    tiers = {e.get("tier") for e in events if e.get("kind") == "tier"}
    assert arm in tiers, tiers
    if arm == "fused":
        # 21 waves of 1, 7 and 64 members: two programs, on the ladders
        # (block rows 64 / 256 / 1,024, dense terms 16)
        keys = {(k[3], k[4]) for k in _wave_program_keys(ss)
                if k[0] == "merged"}
        assert keys == {(64, 16), (256, 16)}, keys
    verdict = compare.compare(ref, pool, answers, probes, 10, _WAVE_LIMITS)
    assert verdict["correct"], verdict["numbers"]
    assert verdict["compared"] == 3 * len(probes)


def _wave_program_keys(ss):
    from elasticsearch_tpu.parallel.sharded import _fused_sharded_for

    keys = {k for k in ss._cache if k[0] == "msearch_merged"}
    fs = getattr(ss, "_fused_msearch", None)
    if fs is not None:
        keys |= {k for k in fs._cache if k[0] == "merged"}
    return keys


def test_the_family_of_wave_programs_is_bounded(wave_corpus, monkeypatch):
    """Several hundred waves of random membership, drawn from a seed, through
    `msearch_wave_begin` on the exact arm: the distinct program keys are those
    the ladders enumerate for this pool (Q tier x Ts tier x B tier of the
    waves drawn), they stop growing, and every look-up was counted."""
    from elasticsearch_tpu.ops.batched import BatchTermSearcher as B
    from elasticsearch_tpu.parallel.sharded import (
        msearch_wave_begin, msearch_wave_fetch, msearch_wave_finish)
    from elasticsearch_tpu.telemetry import metrics

    pack, pool, _ref = wave_corpus
    ss = _arm_searcher(pack, "exact", monkeypatch)
    view = pack.shard_view(0)

    def shape(q):   # (sparse terms, longest sparse term's blocks) of a query
        nbs = [view.term_blocks("body", f"t{t}")[1] for t in pool[q]
               if view.dense_row_of("body", f"t{t}") is None
               and view.term_blocks("body", f"t{t}")[2] > 0]
        return len(nbs), max(nbs, default=0)

    shapes = [shape(q) for q in range(_WAVE_POOL)]
    rng = np.random.default_rng(_WAVE_SEED + 1)
    before = dict(metrics.snapshot()["counters"])
    expected, seen_after = set(), []
    for n_wave in range(300):
        members = rng.choice(_WAVE_POOL, size=int(rng.integers(1, 17)),
                             replace=False)
        st = msearch_wave_begin(ss, "body", _wave_terms(pool, members), k=10)
        msearch_wave_fetch(st)
        msearch_wave_finish(st)
        expected.add((
            B.wave_ts_tier(max(max(shapes[m][0] for m in members), 1)),
            B.wave_b_tier(max(max(shapes[m][1] for m in members), 1)),
            B.wave_q_tier(len(members))))
        seen_after.append(len(_wave_program_keys(ss)))
    keys = _wave_program_keys(ss)
    assert {(k[3], k[4], k[6]) for k in keys} == expected
    # bounded by the ladders, whatever the membership: 5 Q tiers here, the
    # Ts tiers of 1-12 sparse terms, the B tiers of this pack's sparse terms
    assert len(keys) <= 5 * 2 * 2
    assert seen_after[-1] == seen_after[149], "the family still grows"
    after = metrics.snapshot()["counters"]
    added = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    assert added["es.jit.cache.wave_program.misses"] == len(keys)
    assert added["es.jit.cache.wave_program.hits"] == 300 - len(keys)
    assert added["es.jit.cache.msearch_merged.misses"] == len(keys)


def test_an_escalation_reuses_the_tier_programs(wave_corpus, monkeypatch):
    """The fused arm's escalation pads its flagged queries to the batch tier
    (at least ESCALATION_MIN_TIER): 1, 3 and 5 flagged queries run ONE exact
    program, and each escalated row is the row the exact arm gives the query
    alone."""
    from elasticsearch_tpu.parallel import sharded

    pack, pool, _ref = wave_corpus
    exact = _arm_searcher(pack, "exact", monkeypatch)
    ss = _arm_searcher(pack, "fused", monkeypatch)
    fs = sharded._fused_sharded_for(ss)
    assert fs is not None and fs.usable(10)
    members = list(range(8))
    real_finish = sharded._merged_rows_finish
    solo = {}
    for n_flagged in (1, 3, 5):
        st = fs.msearch_merged_begin("body", _wave_terms(pool, members), 10)
        sharded._msearch_merged_fetch(st)
        host = list(st["host"])
        flags = np.zeros_like(np.asarray(host[4]))
        flags[:n_flagged] = True          # as if the fused pass had flagged them
        st["host"] = tuple(host[:4]) + (flags,)
        v, s, d, t = st["finish"](st)
        assert st["extra_dispatches"] == 1
        for at in range(n_flagged):
            q = members[at]
            if q not in solo:
                # alone at the escalation's width: on the CPU a dense
                # product's last bit follows its row count
                alone = _wave_terms(pool, [q]) + [[]] * (
                    sharded.ESCALATION_MIN_TIER - 1)
                # (the arm by name: ES_TPU_FUSED is read at every call)
                st1 = sharded._msearch_merged_arm_begin(
                    exact, "body", alone, 10, impact=False)
                sharded._msearch_merged_fetch(st1)
                v1, s1, d1, t1 = sharded._merged_rows_finish(st1)
                solo[q] = (v1[0], d1[0], int(t1[0]))
            fin = np.isfinite(solo[q][0])
            np.testing.assert_array_equal(v[at], solo[q][0])
            assert (d[at][fin] == solo[q][1][fin]).all()
            assert int(t[at]) == solo[q][2]
    assert sharded._merged_rows_finish is real_finish
    exact_keys = {k for k in ss._cache if k[0] == "msearch_merged"}
    assert {k[6] for k in exact_keys} == {sharded.ESCALATION_MIN_TIER}
    assert len(exact_keys) <= 2      # one Q tier; the Ts tiers of 8 queries
