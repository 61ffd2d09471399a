"""The solo path's one family of `match` programs (PR 38,
`query/nodes.match_params` / `match_tiers`, `ops/scoring.match_scores`).

A match (a term, or a bool of terms on one field) plans as two padded lists,
its dense terms and its sparse terms' posting-block rows, and compiles a
program keyed by the two lists' tiers alone. Held here, on the CPU:

(a) parity with the pure-Python oracle (tests/reference_scorer.py) at every
    tier of both ladders, on one shard and on four (a mesh of four CPU
    devices), exact BM25 and the impact tier: exact totals, every served
    score and every rank within the benchmark's limits;
(b) padding adds nothing: a wider rows tier answers bit for bit the same;
(c) a term absent from a shard, or from every shard; `operator: and`;
    minimum_should_match;
(d) the key holds no entry of any term: a pool of queries compiles one
    program a pair of tiers, and a query of unseen words none;
(e) a wave member that leaves the wave for the solo path compiles within
    the same family;
(f) `scripts/solo_family.py`: the benchmark's pools stay inside the
    ladders' product, a second seed's too.
"""

import itertools
import os
import sys

import numpy as np
import pytest

from elasticsearch_tpu import telemetry
from elasticsearch_tpu.index.mappings import Mappings
from elasticsearch_tpu.parallel import StackedSearcher, make_mesh
from elasticsearch_tpu.parallel.stacked import build_stacked_pack, route_docs
from elasticsearch_tpu.query import nodes

from reference_scorer import Oracle

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAPPING = {"properties": {"body": {"type": "text"}}}
N_DOCS = 2400
DENSE_MIN_DF = 1300
# dense words (~70 % of the documents: the dense tier), mid words (~45 %:
# ~9 blocks of 128 postings on one shard, sparse), rare words (~1 %: a block)
HI = [f"hi{i}" for i in range(20)]
MID = [f"mid{i}" for i in range(40)]
LO = [f"lo{i}" for i in range(200)]


def _docs(n=N_DOCS, seed=38):
    rng = np.random.default_rng(seed)
    docs = []
    for i in range(n):
        words = [w for vocab, p in ((HI, 0.7), (MID, 0.45), (LO, 0.01))
                 for w in vocab if rng.random() < p]
        toks = [w for w in words for _ in range(int(rng.integers(1, 4)))]
        rng.shuffle(toks)
        docs.append((f"d-{i}", {"body": " ".join(toks) or "lo0"}))
    return docs


DOCS = _docs()


@pytest.fixture(scope="module")
def oracle():
    return Oracle([src for _, src in DOCS], Mappings(MAPPING))


def _searcher(shards: int, mode: str):
    """A searcher over DOCS on `shards` shards (four: a mesh of four CPU
    devices), its sparse terms scored exactly or from the impact tier."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("ES_TPU_IMPACT", "force" if mode == "impact" else "0")
        sp = build_stacked_pack(DOCS, Mappings(MAPPING), shards,
                                dense_min_df=DENSE_MIN_DF)
        ss = StackedSearcher(sp, mesh=make_mesh(4) if shards == 4 else None)
    # the global index of (shard, local docid)
    gid = {}
    for s, routed in enumerate(route_docs(DOCS, shards)):
        for local, (doc_id, _) in enumerate(routed):
            gid[(s, local)] = int(doc_id[2:])
    ss.gid = gid
    return ss


_SEARCHERS: dict = {}


def searcher(shards: int, mode: str, monkeypatch=None):
    """The module's searcher of a layout and mode; `monkeypatch` keeps the
    mode for the test's queries (a plan reads ES_TPU_IMPACT too)."""
    if monkeypatch is not None:
        monkeypatch.setenv("ES_TPU_IMPACT",
                           "force" if mode == "impact" else "0")
    if (shards, mode) not in _SEARCHERS:
        _SEARCHERS[shards, mode] = _searcher(shards, mode)
    return _SEARCHERS[shards, mode]


def _words(n_hi, n_mid, n_lo, seed):
    rng = np.random.default_rng(seed)
    return ([str(w) for w in rng.choice(HI, n_hi, replace=False)]
            + [str(w) for w in rng.choice(MID, n_mid, replace=False)]
            + [str(w) for w in rng.choice(LO, n_lo, replace=False)])


# (dense, mid, rare) words: every dense tier (0, 1, 2, 4, 8 and, beyond the
# core, 16 and 32) and every rows tier (8, 16, 32; 128 and 512 beyond) on one
# shard, where a mid word holds ~9 blocks
SHAPES = [(0, 0, 1), (0, 1, 3), (0, 2, 3), (1, 0, 2), (1, 1, 0), (1, 3, 0),
          (2, 0, 1), (2, 1, 2), (2, 2, 2), (3, 0, 1), (3, 1, 1), (4, 3, 0),
          (5, 0, 2), (6, 1, 0), (7, 3, 1), (8, 2, 3), (9, 0, 1), (12, 0, 0),
          (1, 5, 0), (3, 6, 2), (17, 0, 0), (2, 16, 0)]
QUERIES = ([{"match": {"body": " ".join(_words(*s, seed=i))}}
            for i, s in enumerate(SHAPES)]
           + [{"match": {"body": {"query": " ".join(_words(*s, seed=i)),
                                  "operator": "and"}}}
              for i, s in enumerate([(2, 0, 0), (1, 1, 0), (3, 1, 0)])]
           + [{"bool": {"should": [{"term": {"body": w}}
                                   for w in _words(3, 2, 1, seed=99)],
                        "minimum_should_match": 3}},
              # a word no document holds, in a disjunction and a conjunction
              {"match": {"body": " ".join(_words(2, 1, 1, seed=7))
                         + " nosuchword"}},
              {"match": {"body": {"query": "hi1 nosuchword",
                                  "operator": "and"}}},
              {"match": {"body": "nosuchword"}},
              {"match": {"body": {"query": "hi3 mid5 lo7", "boost": 2.5}}}])


def _search(ss, query):
    """One search, planned and run (the request cache's way round it)."""
    return ss.search_batch([{"query": query, "size": 10}])[0]


def _check(res, oracle, query, gid, tol):
    """Exact total; every served document a match of the reference's, its
    score within `tol` of the reference's (score_gap) and not below the
    reference's document of its rank by more than `tol` (rank_gap); served
    order (score desc)."""
    ref, matched = oracle.eval(query)
    assert res.total == len(matched), query
    ranked = sorted(((d, ref.get(d, 0.0)) for d in matched),
                    key=lambda x: (-x[1], x[0]))[:10]
    assert len(res.scores) == len(ranked), query
    for i, (s, d, score) in enumerate(zip(res.doc_shards, res.doc_ids,
                                          res.scores)):
        g = gid[(int(s), int(d))]
        assert g in matched, (query, g)
        assert abs(float(score) - ref[g]) <= tol * ref[g], (query, g)
        assert ranked[i][1] - ref[g] <= tol * ranked[i][1], (query, i)
    assert list(res.scores) == sorted(res.scores, reverse=True)


@pytest.mark.parametrize("mode, tol", [("exact", 1e-5), ("impact", 2e-4)])
@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("qi", range(len(QUERIES)))
def test_parity_with_the_reference_at_every_tier(qi, shards, mode, tol,
                                                 oracle, monkeypatch):
    ss = searcher(shards, mode, monkeypatch)
    query = QUERIES[qi]
    st = ss._agg_dispatch(query=query, size=10)
    tag, fld, key_mode, td, tr = st["keys"][0]
    assert (tag, fld, key_mode) == ("match", "body", mode)
    assert len(set(st["keys"])) == 1        # one key for every shard
    res = _search(ss, query)
    _check(res, oracle, query, ss.gid, tol)
    # a query's answer is the same every time (repeat_diff)
    again = _search(ss, query)
    assert np.array_equal(res.scores.view(np.uint32),
                          again.scores.view(np.uint32))
    assert np.array_equal(res.doc_ids, again.doc_ids)


def test_the_shapes_reach_every_tier_of_both_ladders():
    ss = searcher(1, "exact")
    tiers = {ss._agg_dispatch(query=q, size=10)["keys"][0][3:]
             for q in QUERIES}
    assert {td for td, _ in tiers} >= {0, 1, 2, 4, 8, 16, 32}
    assert {tr for _, tr in tiers} >= {8, 16, 32, 128, 512}
    # the core box, and beyond it the diagonal: (16, 128), (32, 512)
    for td, tr in tiers:
        assert (td <= 8 and tr <= 32) or (td, tr) in {(16, 128), (32, 512)}


@pytest.mark.parametrize("n_dense, n_rows, want", [
    (0, 0, (0, 8)), (0, 1, (0, 8)), (1, 0, (1, 8)), (1, 9, (1, 16)),
    (3, 17, (4, 32)), (5, 8, (8, 8)), (8, 32, (8, 32)),
    (9, 1, (16, 128)), (0, 33, (16, 128)), (16, 128, (16, 128)),
    (17, 1, (32, 512)), (1, 129, (32, 512)), (33, 0, (64, 2048)),
])
def test_the_ladders(n_dense, n_rows, want):
    assert nodes.match_tiers(n_dense, n_rows) == want


def test_the_smallest_rows_tier_is_the_setting_and_moves_the_ladder(tmp_path):
    from elasticsearch_tpu.engine.engine import Engine

    e = Engine(str(tmp_path / "data"))
    try:
        assert nodes.MATCH_MIN_ROWS == 8
        e.settings.update({"transient": {"search.solo.min_rows_tier": 12}})
        assert nodes.MATCH_MIN_ROWS == 16
        assert nodes.match_tiers(1, 3) == (1, 16)
        assert nodes.match_tiers(1, 64) == (1, 64)
        assert nodes.match_tiers(1, 65) == (16, 256)
        assert nodes.match_tiers(9, 1) == (16, 256)
    finally:
        e.settings.update({"transient": {"search.solo.min_rows_tier": 8}})
        e.close()
    assert nodes.MATCH_MIN_ROWS == 8


@pytest.mark.parametrize("shards", [1, 4])
def test_padding_adds_nothing(shards, monkeypatch):
    """The same queries with every rows tier four times wider (a smallest
    tier of 32): the same bits, on other programs."""
    ss = searcher(shards, "exact", monkeypatch)
    queries = QUERIES[:8]
    tiers = [ss._agg_dispatch(query=q, size=10)["keys"][0][4]
             for q in queries]
    narrow = [_search(ss, q) for q in queries]
    monkeypatch.setattr(nodes, "MATCH_MIN_ROWS", 32)
    assert all(ss._agg_dispatch(query=q, size=10)["keys"][0][4] == 32
               for q in queries)
    assert min(tiers) == 8
    wide = [_search(ss, q) for q in queries]
    for a, b in zip(narrow, wide):
        assert a.total == b.total
        assert np.array_equal(a.scores.view(np.uint32),
                              b.scores.view(np.uint32))
        assert np.array_equal(a.doc_ids, b.doc_ids)
        assert np.array_equal(a.doc_shards, b.doc_shards)


def test_the_order_of_the_words_is_not_part_of_the_plan(monkeypatch):
    ss = searcher(1, "impact", monkeypatch)
    words = _words(3, 2, 2, seed=5)
    answers = []
    for perm in list(itertools.permutations(words))[:6]:
        st = ss._agg_dispatch(query={"match": {"body": " ".join(perm)}},
                              size=10)
        answers.append((st["keys"], [np.asarray(p) for p in st["params"]]))
    for keys, params in answers[1:]:
        assert keys == answers[0][0]
        for a, b in zip(params, answers[0][1]):
            assert np.array_equal(a, b)


def test_a_pools_keys_hold_no_entry_of_a_term():
    """Every plan of a pool of matches is ("match", field, mode, dense tier,
    rows tier): no term's kind, bucket or position; the pool compiles one
    program a pair of tiers, and a second pool of other words that lands on
    the same pairs compiles nothing."""
    ss = _searcher(1, "exact")
    rng = np.random.default_rng(12)

    def pool(n):
        return [{"match": {"body": " ".join(_words(
            int(rng.integers(0, 6)), int(rng.integers(0, 3)),
            int(rng.integers(1, 4)), seed=int(rng.integers(1 << 30))))}}
            for _ in range(n)]

    first = pool(40)
    keys = set()
    for q in first:
        key = ss._agg_dispatch(query=q, size=10)["keys"][0]
        assert len(key) == 5 and key[:3] == ("match", "body", "exact")
        assert all(isinstance(x, int) for x in key[3:])
        keys.add(key)
    assert len(ss._cache) == len(keys)
    second = [q for q in pool(40)
              if ss._agg_dispatch(query=q, size=10)["keys"][0] in keys]
    assert second
    programs = len(ss._cache)
    for q in second:
        _search(ss, q)
    assert len(ss._cache) == programs


def test_a_term_absent_from_one_shard_adds_nothing_there(oracle):
    """A rare word some shard lacks: that shard's rows are padding; the
    four-shard answer is the reference's, its plan one program."""
    ss = searcher(4, "exact")
    routed = route_docs(DOCS, 4)
    held = {w: {s for s, docs in enumerate(routed)
                for _, src in docs if w in src["body"].split()}
            for w in LO}
    word = next(w for w, shards in held.items() if 0 < len(shards) < 4)
    query = {"match": {"body": f"{word} hi2 mid3"}}
    st = ss._agg_dispatch(query=query, size=10)
    assert len(set(st["keys"])) == 1
    _check(_search(ss, query), oracle, query, ss.gid, 1e-5)


def test_a_search_adds_its_rows_to_the_counters():
    ss = searcher(1, "exact")

    def counters():
        c = telemetry.metrics.snapshot()["counters"]
        return (c.get("es.search.solo.rows", 0),
                c.get("es.search.solo.padded_rows", 0))

    r0, p0 = counters()
    st = ss._agg_dispatch(query={"match": {"body": "hi1 hi2 lo3"}}, size=10)
    r1, p1 = counters()
    _, _, _, td, tr = st["keys"][0]
    assert (td, tr) == (2, 8)
    assert (r1 - r0, p1 - p0) == (2 + 1, td + tr)
    # a query that is no match gathers none of the family's rows
    ss._agg_dispatch(query={"match_all": {}}, size=10)
    assert counters() == (r1, p1)


def test_plan_key_takes_the_largest_shards_tiers():
    a = ("match", "body", "impact", 4, 8)
    b = ("match", "body", "impact", 4, 32)
    assert nodes.plan_key([a, b, a, a]) == (b,) * 4
    nested = [("bool", ((a,), (), (), ()), 0), ("bool", ((b,), (), (), ()), 0)]
    assert nodes.plan_key(nested) == (nested[1],) * 2
    # keys that differ otherwise stay each shard's own
    other = [("prefix", "body", False, 4), ("prefix", "body", False, 8)]
    assert nodes.plan_key(other) == tuple(other)
    assert nodes.plan_key([a]) == (a,)


def test_a_wave_member_that_leaves_the_wave_compiles_within_the_family(
        tmp_path):
    """Members of a served wave that take the solo path (a conjunction: the
    generic lane; a request the wave does not serve: the fallback, the full
    solo `search`) plan within the same family as a solo search: every
    program is a family member, and a solo search of other words on the
    same tiers compiles nothing."""
    from concurrent.futures import wait

    from elasticsearch_tpu.engine.engine import Engine

    e = Engine(str(tmp_path / "data"))
    try:
        idx = e.create_index("w", {"properties": {"body": {"type": "text"}}})
        for doc_id, src in DOCS[:600]:
            idx.index_doc(doc_id, src)
        idx.refresh()
        svc = e.serving
        # the generic lane: a conjunction is no term disjunction
        entry = svc.classify("w", {"query": {"match": {"body": {
            "query": "hi1 mid2 lo3", "operator": "and"}}}, "size": 5}, {})
        fut = svc.submit(entry)
        wait([fut], timeout=120)
        assert fut.result(timeout=1)["hits"]["total"]["value"] > 0
        # the engine's fallback inside a wave: the full solo `search`
        job = idx.search_wave_begin([{"query": {"match": {"body": "hi4 mid5"}},
                                      "size": 5, "runtime_mappings": {}}])
        idx.search_wave_fetch(job)
        assert idx.search_wave_finish(job)[0]["hits"]["total"]["value"] > 0
        # the service's `fallback_solo`: the engine's solo entry point
        e.search_multi("w", query={"match": {"body": "lo11 hi7"}}, size=5)
        ss = idx.searcher
        keys = [k for k in ss._cache if isinstance(k, tuple) and len(k) == 5
                and isinstance(k[0], tuple)]
        assert keys
        for cache_key in keys:
            plan = cache_key[0][0]
            assert plan[0] == "match" and len(plan) == 5, cache_key
        programs = len(ss._cache)
        e.search_multi("w", query={"match": {"body": {
            "query": "hi6 mid7 lo8", "operator": "and"}}}, size=5)
        e.search_multi("w", query={"match": {"body": "hi9 mid10"}}, size=5)
        assert len(ss._cache) == programs
        svc.stop()
    finally:
        e.close()


def _solo_family():
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    try:
        import solo_family
    finally:
        sys.path.remove(os.path.join(ROOT, "scripts"))
    return solo_family


@pytest.mark.parametrize("workload, bound", [("passage.solo.fresh", 48),
                                             ("passage.solo.c8", 30)])
def test_a_second_seeds_pool_stays_inside_the_ladders_product(workload,
                                                              bound):
    """The benchmark's own pools at their size (294,912 passages; host
    only): the family reached, the product of the ladders for queries of
    1-12 words and a second seed's pool inside that product."""
    out = _solo_family().count(workload, 3800000011, 3800000012)
    for name in ("first", "second"):
        o = out[name]
        assert len(o["family"]) <= bound
        assert set(o["family"]) <= o["product"]
        assert o["per_term_shapes"] > 2 * len(o["family"])
    assert len(out["first"]["product"]) <= bound
    assert out["second_outside_product"] == []
    if workload == "passage.solo.fresh":
        # a pool as varied as the dev set reaches the whole product
        assert set(out["first"]["family"]) == out["first"]["product"]
