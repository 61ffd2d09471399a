"""PR 7: device-resident quantized ANN (elasticsearch_tpu/ann/).

The recall@10 harness vs the exact oracle across similarities and
quantization tiers, deletes through the live mask, the exact tail tier
for vectors added after the index build, the engine's tiered
(base-ANN + tail-exact) knn path under incremental refresh, filtered
kNN with oversample + post-filter + too-selective escalation, the
gather-scan's bandwidth attribution, and the ann_gather_scan cost model
against hand-computed values. Big sweeps ride the `slow` marker."""

import numpy as np
import pytest

from elasticsearch_tpu.ann import AnnSearcher, build_ann
from elasticsearch_tpu.engine import Engine

SIMS = ("cosine", "dot_product", "l2_norm", "max_inner_product")


def _clustered_corpus(rng, n=4000, dims=32, ncl=25):
    """Mixture-of-gaussians corpus — the regime IVF partitioning is FOR
    (real embedding spaces cluster; uniform noise is the known worst
    case and is covered by the full-probe exactness tests instead)."""
    centers = rng.normal(size=(ncl, dims)).astype(np.float32) * 4.0
    assign = rng.integers(0, ncl, size=n)
    vecs = centers[assign] + rng.normal(size=(n, dims)).astype(np.float32) * 0.6
    return vecs.astype(np.float32)


def _oracle(vecs, sq, q, sim, k, live=None):
    """Exact top-k (score desc, docid asc) via the scalar score fn."""
    import jax.numpy as jnp

    from elasticsearch_tpu.ops.vector import knn_scores

    sc = np.asarray(knn_scores(jnp.asarray(vecs), jnp.asarray(sq),
                               jnp.asarray(q), sim))
    if live is not None:
        sc = np.where(live, sc, -np.inf)
    return np.lexsort((np.arange(len(sc)), -sc))[:k]


def _recall_at_10(searcher, vecs, sq, queries, sim, live=None, **kw):
    v, ids, _t = searcher.search(queries, 10, **kw)
    got = 0.0
    for b, q in enumerate(queries):
        truth = set(_oracle(vecs, sq, q, sim, 10, live).tolist())
        got += len(truth & set(int(x) for x in ids[b])) / 10.0
    return got / len(queries)


# ---------------------------------------------------------------------------
# recall@10 vs the exact oracle — the acceptance criterion
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sim", SIMS)
def test_recall_at_default_nprobe(rng, sim):
    vecs = _clustered_corpus(rng)
    sq = (vecs * vecs).sum(1)
    ann = build_ann(vecs, np.ones(len(vecs), bool), nlist=25)
    s = AnnSearcher(ann, vecs, sq, sim)
    queries = vecs[rng.integers(0, len(vecs), 24)] + rng.normal(
        size=(24, vecs.shape[1])).astype(np.float32) * 0.1
    # default nprobe (coverage of num_candidates=100) — the C4 bench arm
    recall = _recall_at_10(s, vecs, sq, queries, sim, num_candidates=100)
    assert recall >= 0.95, f"[{sim}] recall@10 {recall} < 0.95"


@pytest.mark.parametrize("tier", ("int8", "bf16"))
def test_quantization_tiers_recall_and_exact_scores(rng, tier):
    vecs = _clustered_corpus(rng, n=3000)
    sq = (vecs * vecs).sum(1)
    ann = build_ann(vecs, np.ones(len(vecs), bool), nlist=20)
    s = AnnSearcher(ann, vecs, sq, "cosine", tier=tier)
    queries = vecs[:8] + 0.05 * rng.normal(size=(8, 32)).astype(np.float32)
    recall = _recall_at_10(s, vecs, sq, queries, "cosine",
                           num_candidates=100)
    assert recall >= 0.95, f"[{tier}] recall {recall}"
    # returned SCORES are exact f32 regardless of the selection tier
    v, ids, _ = s.search(queries, 10, num_candidates=100)
    import jax.numpy as jnp

    from elasticsearch_tpu.ops.vector import knn_scores

    sc = np.asarray(knn_scores(jnp.asarray(vecs), jnp.asarray(sq),
                               jnp.asarray(queries[0]), "cosine"))
    np.testing.assert_allclose(v[0], sc[ids[0]], rtol=2e-6, atol=2e-6)


def test_full_probe_is_exact_every_similarity(rng):
    vecs = rng.normal(size=(900, 24)).astype(np.float32)  # worst case
    sq = (vecs * vecs).sum(1)
    ann = build_ann(vecs, np.ones(900, bool), nlist=8)
    queries = rng.normal(size=(6, 24)).astype(np.float32)
    for sim in SIMS:
        s = AnnSearcher(ann, vecs, sq, sim)
        v, ids, totals = s.search(queries, 10, nprobe=8)
        assert (totals == 900).all()
        for b in range(len(queries)):
            assert ids[b].tolist() == _oracle(
                vecs, sq, queries[b], sim, 10).tolist(), sim


# ---------------------------------------------------------------------------
# deletes + the exact tail tier
# ---------------------------------------------------------------------------

def test_live_mask_deletes(rng):
    vecs = _clustered_corpus(rng, n=2000)
    sq = (vecs * vecs).sum(1)
    ann = build_ann(vecs, np.ones(len(vecs), bool), nlist=16)
    s = AnnSearcher(ann, vecs, sq, "l2_norm")
    q = vecs[7:8]
    _, ids, _ = s.search(q, 5, nprobe=16)
    assert ids[0][0] == 7
    live = np.ones(len(vecs), bool)
    live[ids[0][:3]] = False
    s.set_live(live)
    v, ids2, totals = s.search(q, 5, nprobe=16)
    assert not (set(int(x) for x in ids[0][:3]) & set(int(x) for x in ids2[0]))
    assert ids2[0].tolist() == _oracle(vecs, sq, q[0], "l2_norm", 5,
                                       live).tolist()
    assert totals[0] == live.sum()


def test_tail_vectors_never_degrade_recall(rng):
    base = _clustered_corpus(rng, n=1500)
    ann = build_ann(base, np.ones(len(base), bool), nlist=12)
    # 200 appended vectors in a REGION THE INDEX NEVER SAW — a pure
    # partition probe could not find them; the exact tail tier must
    full = np.concatenate(
        [base, rng.normal(size=(200, 32)).astype(np.float32) + 40.0])
    sq = (full * full).sum(1)
    s = AnnSearcher(ann, full, sq, "l2_norm")
    assert s.built_n == 1500
    queries = full[1500 + rng.integers(0, 200, 6)]
    recall = _recall_at_10(s, full, sq, queries, "l2_norm",
                           num_candidates=100)
    assert recall == 1.0, f"tail recall {recall}"
    # tail totals count into the candidate totals
    _, _, totals = s.search(queries[:1], 10, nprobe=2)
    assert totals[0] > 200


# ---------------------------------------------------------------------------
# engine: incremental refresh keeps the base ANN + exact tail merge
# ---------------------------------------------------------------------------

def _ann_engine(rng, n=800, dims=16, nlist=10, similarity="l2_norm"):
    e = Engine(None)
    e.create_index("v", {"properties": {
        "vec": {"type": "dense_vector", "dims": dims,
                "similarity": similarity,
                "index_options": {"type": "ivf", "nlist": nlist}},
        "tag": {"type": "keyword"},
    }})
    idx = e.indices["v"]
    vecs = _clustered_corpus(rng, n=n, dims=dims, ncl=nlist)
    for i in range(n):
        idx.index_doc(str(i), {"vec": [float(x) for x in vecs[i]],
                               "tag": f"t{i % 4}"})
    idx.refresh()
    return e, idx, vecs


def test_incremental_refresh_tail_knn(rng):
    e, idx, vecs = _ann_engine(rng)
    assert idx.searcher.sp.vectors["vec"].ann is not None
    # write a few docs -> incremental refresh builds a TAIL, not a rebuild
    far = rng.normal(size=(5, 16)).astype(np.float32) + 30.0
    for j in range(5):
        idx.index_doc(f"new{j}", {"vec": [float(x) for x in far[j]],
                                  "tag": "fresh"})
    idx.refresh()
    assert idx._tail is not None, "expected an incremental (tail) refresh"
    r = idx.search(knn={"field": "vec", "query_vector":
                        [float(x) for x in far[2]], "k": 3})
    # the knn search must see the tail docs AND must not have merged it
    assert r["hits"]["hits"][0]["_id"] == "new2"
    # the (base, tail) merge honors k: at most k hits, total clamped
    # (regression: the merge once sliced with the unclamped size)
    assert len(r["hits"]["hits"]) == 3
    assert r["hits"]["total"]["value"] == 3
    assert idx._tail is not None, "knn search forced a tier merge"
    # deletes flip base live bits; the dead doc disappears from knn
    q0 = [float(x) for x in vecs[11]]
    top = idx.search(knn={"field": "vec", "query_vector": q0, "k": 1,
                          "nprobe": 10})["hits"]["hits"][0]["_id"]
    idx.delete_doc(top)
    idx.refresh()
    r2 = idx.search(knn={"field": "vec", "query_vector": q0, "k": 3,
                         "nprobe": 10})
    assert top not in [h["_id"] for h in r2["hits"]["hits"]]


def test_filtered_knn_stays_on_ann_path(rng):
    e, idx, vecs = _ann_engine(rng)
    q = [float(x) for x in vecs[3]]
    r = idx.search(knn={"field": "vec", "query_vector": q, "k": 5,
                        "num_candidates": 200,
                        "filter": {"term": {"tag": "t1"}}})
    hits = r["hits"]["hits"]
    assert len(hits) == 5
    assert all(int(h["_id"]) % 4 == 1 for h in hits)
    # parity with the forced-exact filter path at full coverage
    r2 = idx.search(knn={"field": "vec", "query_vector": q, "k": 5,
                         "num_candidates": 800, "nprobe": 10,
                         "filter": {"term": {"tag": "t1"}}})
    assert [h["_id"] for h in r2["hits"]["hits"]] == [
        h["_id"] for h in hits]


def test_too_selective_filter_escalates_to_exact(rng):
    e, idx, vecs = _ann_engine(rng)
    # one doc with a unique tag, placed FAR from the query so no probe
    # reaches it: only the exact escalation can satisfy the filter
    lone = rng.normal(size=16).astype(np.float32) + 25.0
    idx.index_doc("lone", {"vec": [float(x) for x in lone], "tag": "rare"})
    idx.refresh()
    idx.searcher  # fold the tail: "lone" must live in the ANN-indexed base
    q = [float(x) for x in vecs[0]]
    r = idx.search(knn={"field": "vec", "query_vector": q, "k": 1,
                        "nprobe": 1,
                        "filter": {"term": {"tag": "rare"}}})
    assert [h["_id"] for h in r["hits"]["hits"]] == ["lone"]


def test_similarity_threshold_on_ann_path(rng):
    e, idx, vecs = _ann_engine(rng)
    q = [float(x) for x in vecs[5]]
    r = idx.search(knn={"field": "vec", "query_vector": q, "k": 10,
                        "num_candidates": 200, "similarity": 0.5})
    # l2 threshold 0.5 -> score floor 1/(1+0.25); every hit clears it
    assert all(h["_score"] >= 1.0 / 1.25 - 1e-6
               for h in r["hits"]["hits"])
    assert r["hits"]["hits"][0]["_id"] == "5"


# ---------------------------------------------------------------------------
# attribution: the quantized scan records bw_util per dispatch
# ---------------------------------------------------------------------------

def test_gather_scan_records_bandwidth_utilization(rng):
    from elasticsearch_tpu.telemetry import collect_profile_events

    vecs = _clustered_corpus(rng, n=2000)
    sq = (vecs * vecs).sum(1)
    ann = build_ann(vecs, np.ones(len(vecs), bool), nlist=16)
    s = AnnSearcher(ann, vecs, sq, "cosine")
    with collect_profile_events() as events:
        s.search(vecs[:16], 10, num_candidates=100)
    kernels = {e["kernel"]: e for e in events if e["kind"] == "kernel"}
    scan = kernels["ann.gather_scan"]
    # a CPU run gives counts from shapes; a utilisation needs the chip
    assert scan["bytes"] > 0 and scan["flops"] > 0
    assert scan["bw_util"] >= 0 and scan["mfu"] >= 0
    assert kernels["ann.centroid_probe"]["flops"] > 0
    assert kernels["ann.rescore"]["bytes"] > 0


def test_ann_gather_scan_cost_hand_computed():
    from elasticsearch_tpu.monitoring.costmodel import ann_gather_scan_cost

    b, p, l, d = 64, 8, 512, 384
    slots = b * p * l
    c8 = ann_gather_scan_cost(b, p, l, d, tier="int8")
    assert c8["flops"] == 2.0 * slots * d + 2.0 * slots + 2.0 * slots
    assert c8["bytes"] == slots * (d + 8) + slots * 12 + b * d * 4
    cb = ann_gather_scan_cost(b, p, l, d, tier="bf16")
    assert cb["flops"] == 4.0 * slots * d + 2.0 * slots
    assert cb["bytes"] == slots * 4 * d + slots * 12 + b * d * 4
    # the tiering trade on record: int8 moves ~4x fewer tile bytes
    assert c8["bytes"] < cb["bytes"] / 3


# ---------------------------------------------------------------------------
# slow sweeps: bigger corpus, nprobe/recall frontier, both tiers
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("tier", ("int8", "bf16"))
def test_recall_frontier_sweep(rng, tier):
    vecs = _clustered_corpus(rng, n=40_000, dims=64, ncl=64)
    sq = (vecs * vecs).sum(1)
    ann = build_ann(vecs, np.ones(len(vecs), bool), nlist=64)
    s = AnnSearcher(ann, vecs, sq, "cosine", tier=tier)
    queries = vecs[rng.integers(0, len(vecs), 32)] + 0.05 * rng.normal(
        size=(32, 64)).astype(np.float32)
    last = 0.0
    for nprobe in (1, 4, 16, 64):
        recall = _recall_at_10(s, vecs, sq, queries, "cosine",
                               nprobe=nprobe)
        assert recall >= last - 0.02, (nprobe, recall, last)
        last = max(last, recall)
    assert last == 1.0  # full probe converges to exact


@pytest.mark.slow
def test_engine_recall_sweep_all_similarities(rng):
    for sim in ("cosine", "dot_product", "l2_norm"):
        e, idx, vecs = _ann_engine(rng, n=5000, dims=32, nlist=32,
                                   similarity=sim)
        got = 0.0
        trials = 20
        for t in range(trials):
            q = [float(x) for x in vecs[rng.integers(0, len(vecs))]]
            approx = idx.search(knn={"field": "vec", "query_vector": q,
                                     "k": 10, "num_candidates": 200})
            exact = idx.search(knn={"field": "vec", "query_vector": q,
                                    "k": 10, "nprobe": 32,
                                    "num_candidates": 5000})
            a = [h["_id"] for h in approx["hits"]["hits"]]
            b = {h["_id"] for h in exact["hits"]["hits"]}
            got += len(set(a) & b) / 10.0
        assert got / trials >= 0.95, (sim, got / trials)
