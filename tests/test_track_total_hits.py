"""`track_total_hits` on the one way a disjunction runs: every count is
exact, so `true`, a threshold, a value above every df and an absent
parameter all answer `{value: <count>, relation: "eq"}`, `false` omits
`hits.total`, and none of them changes which documents come back.

Reference: SearchSourceBuilder.trackTotalHitsUpTo; the reference answers a
threshold with a lower bound ("gte") when its collector stops counting
early. Nothing here stops early (DIVERGENCES.md).
"""

import numpy as np
import pytest

from elasticsearch_tpu.engine import Engine
from elasticsearch_tpu.index.mappings import Mappings

from reference_scorer import Oracle

MAPPING = {"properties": {"body": {"type": "text"}}}
QUERY = {"match": {"body": "rare1 rare2 com1 com2"}}
ABSENT = object()
VALUES = {"absent": ABSENT, "true": True, "false": False,
          "below_top_df": 50, "above_every_df": 10_000_000}
N_BASE, N_TAIL = 1200, 100


def _skewed_corpus(n_docs=12000, seed=7, n_rare=6):
    """Rare high-idf terms decide the top-k; common low-idf terms carry
    long postings lists that match about three documents in four."""
    rng = np.random.default_rng(seed)
    rare_docs = {t: set(rng.choice(n_docs, n_rare, replace=False))
                 for t in ("rare1", "rare2")}
    mid_docs = set(rng.choice(n_docs, max(n_docs // 30, 1), replace=False))
    docs = []
    for i in range(n_docs):
        words = ["filler%d" % rng.integers(0, 200)] * int(rng.integers(2, 6))
        for t in ("com1", "com2"):
            if rng.random() < 0.5:
                words += [t] * int(rng.integers(1, 4))
        if i in mid_docs:
            words.append("mid1")
        for t, members in rare_docs.items():
            if i in members:
                words += [t, t, "com1", "com2"]
        rng.shuffle(words)
        docs.append((f"d{i}", {"body": " ".join(words)}))
    return docs


def _index(name, docs, shards):
    idx = Engine(None).create_index(
        name, mappings=MAPPING, settings={"number_of_shards": shards})
    for did, src in docs:
        idx.index_doc(did, src)
    idx.refresh()
    return idx


@pytest.fixture(scope="module")
def corpus():
    return _skewed_corpus(n_docs=N_BASE + N_TAIL, seed=5)


@pytest.fixture(scope="module")
def oracle_hits(corpus):
    """The reference scorer's answer over the whole corpus: (count, the
    ten best scores)."""
    scores, match = Oracle([src for _, src in corpus],
                           Mappings(MAPPING)).eval(QUERY)
    return len(match), sorted(scores.values(), reverse=True)[:10]


@pytest.fixture(scope="module")
def layouts(corpus):
    """The three shapes a search is formatted from: one shard, two shards
    (`_format_generic_hits`), and a base with a tail segment after an
    incremental refresh (`_tiered_merge`)."""
    tiered = _index("tth_tiered", corpus[:N_BASE], 2)
    for did, src in corpus[N_BASE:]:
        tiered.index_doc(did, src)
    tiered.refresh()
    assert tiered._tails, "the second refresh should have built a tail"
    return {"one_shard": _index("tth_one", corpus, 1),
            "two_shards": _index("tth_two", corpus, 2),
            "base_tail": tiered}


def _search(idx, value):
    kw = {} if value is ABSENT else {"track_total_hits": value}
    return idx.search(query=QUERY, size=10, **kw)["hits"]


@pytest.mark.parametrize("layout", ["one_shard", "two_shards", "base_tail"])
@pytest.mark.parametrize("value", list(VALUES))
def test_track_total_hits_contract(layouts, oracle_hits, layout, value):
    idx = layouts[layout]
    count, best = oracle_hits
    assert count > 50, "the threshold case needs a df above it"
    hits = _search(idx, VALUES[value])
    if VALUES[value] is False:
        assert "total" not in hits
    else:
        assert hits["total"] == {"value": count, "relation": "eq"}
    np.testing.assert_allclose(
        [h["_score"] for h in hits["hits"]], best, rtol=1e-5)
    # the parameter only formats: the documents are those of an exact count
    exact = _search(idx, True)
    assert [(h["_id"], h["_score"]) for h in hits["hits"]] == \
           [(h["_id"], h["_score"]) for h in exact["hits"]]
    assert hits["max_score"] == exact["max_score"]


def test_total_excludes_deleted_docs_on_base_and_tail(corpus):
    idx = _index("tth_deletes", corpus[:N_BASE], 2)
    for did, src in corpus[N_BASE:]:
        idx.index_doc(did, src)
    idx.refresh()
    # deleted in place on both tiers; few enough that the refresh stays
    # incremental (engine._can_refresh_incremental)
    gone = {did for did, _ in corpus[:N_BASE:12]} | \
           {did for did, _ in corpus[N_BASE::4]}
    for did in gone:
        idx.delete_doc(did)
    idx.refresh()
    assert idx._tails, "deletes alone must not fold the tail into the base"
    left = [src for did, src in corpus if did not in gone]
    _, match = Oracle(left, Mappings(MAPPING)).eval(QUERY)
    for value in (ABSENT, True, 50):
        hits = _search(idx, value)
        assert hits["total"] == {"value": len(match), "relation": "eq"}
        assert not {h["_id"] for h in hits["hits"]} & gone
    assert "total" not in _search(idx, False)
