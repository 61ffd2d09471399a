"""Batch-vectorized ingest analysis (PR 16): term-stream parity, the
analyze/build overlap pipeline, and the monitoring/SLO surface.

The contract under test: every batched/device analysis path emits the
EXACT token stream of the per-doc `Analyzer.analyze()` oracle — same
terms, same positions (stopword gaps, +100 multi-value gap chaining,
overlong-token splits, the POS_L stored-position bound), same
field-length norms — across standard/custom analyzers, unicode,
empty/0-token values and multi-value docs. Plus: the batched-analyzer
memo invalidates with the analysis generation; the depth-1
analyze(k) ∥ build(k−1) overlap produces identical packs and leaves
worker spans in the RefreshProfile; and the new slo.write
analyze-fraction objective + health dominant-stage remedy fire."""

import numpy as np
import pytest

from elasticsearch_tpu import xpack
from elasticsearch_tpu.analysis.analyzers import (
    ENGLISH_STOP_WORDS,
    KeywordAnalyzer,
    SimpleAnalyzer,
    StandardAnalyzer,
    StopAnalyzer,
    WhitespaceAnalyzer,
    get_analyzer,
)
from elasticsearch_tpu.analysis.batched import (
    BatchedAnalyzer,
    analyze_burst,
    analyze_mode,
)
from elasticsearch_tpu.engine import Engine
from elasticsearch_tpu.index.mappings import Mappings
from elasticsearch_tpu.index.pack import POS_L, PackBuilder
from elasticsearch_tpu.monitoring.refresh_profile import (
    collect_build_stages,
)
from elasticsearch_tpu.parallel.stacked import (
    build_stacked_pack_routed,
    route_docs,
)
from elasticsearch_tpu.telemetry import metrics

# every structural hazard the fast paths must prove they handle (or
# fall back per value): case, stopwords, apostrophe joins (single and
# multi), non-ASCII + NFC forms, digits/underscores, overlong tokens,
# empty and whitespace-only values
TEXTS = [
    "The quick brown Fox jumps over the lazy dog",
    "",
    "   \t\n  ",
    "don't stop BELIEVIN' it's l'heure",
    "a'b'c rock'n'roll ''quoted'' trailin'",
    "café résumé naïve",
    "café decomposed vs café composed",
    "日本語のテキスト and ascii words",
    "under_scores and-hyphens 42 3.14 v2 x86_64",
    "x" * 300 + " short tail",
    "the and of to in is",
    "MiXeD CaSe TEXT lower UPPER",
    ("t1 t2 t3 " * 30).strip(),
    "ß groß STRASSE",
    "emoji 😀 mixed in",
    "solo",
]


def _analyzers():
    return [
        ("standard", StandardAnalyzer()),
        ("standard-stop", StandardAnalyzer(stopwords=ENGLISH_STOP_WORDS)),
        ("standard-mtl8", StandardAnalyzer(max_token_length=8)),
        ("whitespace", WhitespaceAnalyzer()),
        ("simple", SimpleAnalyzer()),
        ("stop", StopAnalyzer()),
        ("keyword", KeywordAnalyzer()),
        ("english", get_analyzer("english")),
    ]


# ---------------------------------------------------------------------------
# value-level stream parity: every analyzer, every mode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["host", "batched", "device"])
@pytest.mark.parametrize(
    "an", [a for _, a in _analyzers()],
    ids=[n for n, _ in _analyzers()])
def test_value_stream_parity_vs_oracle(an, mode):
    ba = BatchedAnalyzer(an)
    vt = ba.analyze_values(list(TEXTS), mode=mode)
    assert vt.terms.size == int(vt.counts.sum())
    for i, v in enumerate(TEXTS):
        toks = an.analyze(v)
        sel = vt.value_idx == i
        assert list(vt.terms[sel]) == [t.term for t in toks], (i, v)
        assert vt.pos_pre[sel].tolist() == [t.position for t in toks], (i, v)
        assert int(vt.counts[i]) == len(toks)
        assert int(vt.last_pos[i]) == max(
            (t.position for t in toks), default=-1)


def test_device_basis_engages_and_falls_back_per_value():
    """ES_TPU_ANALYZE=device forces the hash kernel for the eligible
    analyzer; ineligible values (non-ASCII, multi-apostrophe runs,
    overlong tokens) re-analyze on host and merge back in value order."""
    ba = BatchedAnalyzer(StandardAnalyzer())
    assert ba.device_eligible
    vt = ba.analyze_values(list(TEXTS), mode="device")
    assert vt.basis == "device"
    an = StandardAnalyzer()
    for i, v in enumerate(TEXTS):
        sel = vt.value_idx == i
        assert list(vt.terms[sel]) == [t.term for t in an.analyze(v)], (i, v)
    # a non-eligible analyzer never claims the device basis
    vt2 = BatchedAnalyzer(StopAnalyzer()).analyze_values(
        list(TEXTS), mode="device")
    assert vt2.basis == "host"


def test_auto_mode_defaults_and_small_burst_stays_host(monkeypatch):
    monkeypatch.delenv("ES_TPU_ANALYZE", raising=False)
    assert analyze_mode() == "auto"
    monkeypatch.setenv("ES_TPU_ANALYZE", "bogus")
    assert analyze_mode() == "auto"
    monkeypatch.setenv("ES_TPU_ANALYZE", "HOST")
    assert analyze_mode() == "host"
    # auto + a burst far under ES_TPU_ANALYZE_MIN bytes: no device trip
    monkeypatch.delenv("ES_TPU_ANALYZE", raising=False)
    vt = BatchedAnalyzer(StandardAnalyzer()).analyze_values(
        ["tiny burst"], mode="auto")
    assert vt.basis == "host"


# ---------------------------------------------------------------------------
# builder-state parity: add_documents_batch == N * add_document
# ---------------------------------------------------------------------------

_MAPPING = {
    "properties": {
        "body": {"type": "text"},
        "title": {"type": "text", "analyzer": "my_stop"},
        "notes": {"type": "text", "analyzer": "english"},
        "tag": {"type": "keyword"},
        "n": {"type": "integer"},
    }
}


def _mappings():
    m = Mappings(_MAPPING)
    m.set_analysis({"my_stop": StandardAnalyzer(stopwords=["the", "of"])})
    return m


def _docs():
    docs = []
    for i, t in enumerate(TEXTS):
        docs.append({"body": t, "title": t, "notes": t,
                     "tag": f"k{i % 3}", "n": i})
    # multi-value docs: the +100 position gap must chain identically
    docs.append({"body": list(TEXTS[:5]), "title": ["one two", "", "three"]})
    docs.append({"body": ["", "   "], "title": []})
    docs.append({"tag": "no-text"})
    return docs


def _dict_state(b):
    return (b.postings, b.positions, b.doc_field_lengths, b.docvalue_raw)


def _build_ref(m, parsed, ids):
    ref = PackBuilder(m, use_native=False)
    for p, d in zip(parsed, ids):
        ref.add_document(p, doc_id=d)
    return ref


@pytest.mark.parametrize("mode", ["host", "batched", "device"])
def test_builder_state_parity(mode, monkeypatch):
    m = _mappings()
    parsed = [m.parse_document(d) for d in _docs()]
    ids = [f"d{i}" for i in range(len(parsed))]
    ref = _build_ref(m, parsed, ids)
    monkeypatch.setenv("ES_TPU_ANALYZE", mode)
    bat = PackBuilder(m, use_native=False)
    got = bat.add_documents_batch(parsed, doc_ids=ids)
    assert got == list(range(len(parsed)))
    assert _dict_state(bat) == _dict_state(ref)


def test_native_pack_parity(monkeypatch):
    """The native-accumulator lane of _ingest_text_burst feeds the C++
    builder the same unfiltered token/position stream as
    _add_text_native; the BUILT packs must agree on term stats and
    field-length norms."""
    m = Mappings({"properties": {"body": {"type": "text"}}})
    probe = PackBuilder(m)
    if probe._native is None:
        pytest.skip("native accumulator not built in this environment")
    parsed = [m.parse_document({"body": t}) for t in TEXTS if t.strip()]
    ref = PackBuilder(m)
    for p in parsed:
        ref.add_document(p)
    monkeypatch.setenv("ES_TPU_ANALYZE", "batched")
    bat = PackBuilder(m)
    bat.add_documents_batch(parsed)
    pr, pb = ref.build(), bat.build()
    assert pr.num_docs == pb.num_docs
    sr, sb = pr.field_stats["body"], pb.field_stats["body"]
    assert sr == sb


def test_pos_bound_and_long_doc_parity(monkeypatch):
    """Positions at/after POS_L-64 are dropped from storage but still
    count toward tf and the field-length norm — identically in both
    lanes. 900 values x ~200 position increment pushes well past the
    bound."""
    m = Mappings({"properties": {"body": {"type": "text"}}})
    value = " ".join(f"w{j}" for j in range(100))  # last_pos 99 -> inc 200
    parsed = [m.parse_document({"body": [value] * 900}),
              m.parse_document({"body": "plain follow-up doc"})]
    ref = _build_ref(m, parsed, [None, None])
    monkeypatch.setenv("ES_TPU_ANALYZE", "batched")
    bat = PackBuilder(m, use_native=False)
    bat.add_documents_batch(parsed)
    assert _dict_state(bat) == _dict_state(ref)
    # sanity: the bound actually engaged (stored < emitted)
    stored = sum(len(pl) for pl in bat.positions[("body", "w0")].values())
    assert stored < 900
    assert bat.doc_field_lengths["body"][0] == (0, 900 * 100)


# ---------------------------------------------------------------------------
# stage attribution + kernel accounting
# ---------------------------------------------------------------------------

def test_mode_stage_attribution_and_kernel_counters(monkeypatch):
    m = Mappings({"properties": {"body": {"type": "text"}}})
    parsed = [m.parse_document({"body": t}) for t in TEXTS]
    monkeypatch.setenv("ES_TPU_ANALYZE", "host")
    with collect_build_stages() as c_host:
        PackBuilder(m, use_native=False).add_documents_batch(
            [dict(p) for p in parsed])
    assert "analyze" in c_host.stages
    assert "build.analyze" not in c_host.stages
    monkeypatch.setenv("ES_TPU_ANALYZE", "batched")
    before = metrics.snapshot()["counters"].get(
        "es.kernel.build.analyze.flops", 0.0)
    with collect_build_stages() as c_bat:
        PackBuilder(m, use_native=False).add_documents_batch(
            [dict(p) for p in parsed])
    assert "build.analyze" in c_bat.stages
    assert "analyze" not in c_bat.stages
    # the dispatch is costed: the bytes-based KERNEL_COSTS entry turned
    # the burst's nbytes into flop/byte counters like any build kernel
    after = metrics.snapshot()["counters"].get(
        "es.kernel.build.analyze.flops", 0.0)
    assert after > before


# ---------------------------------------------------------------------------
# batched-analyzer memo vs analysis generation (satellite: cache
# invalidation asserts)
# ---------------------------------------------------------------------------

def test_batched_memo_invalidates_with_analysis_generation():
    m = Mappings({"properties": {"body": {
        "type": "text", "analyzer": "my",
        "fields": {"sub": {"type": "text", "analyzer": "my"}}}}})
    m.set_analysis({"my": StandardAnalyzer()})
    gen = m.analysis_generation
    ft = m.fields["body"]
    sub = ft.fields["sub"]
    ba = ft.get_batched_analyzer()
    bs = sub.get_batched_analyzer()
    assert ft.get_batched_analyzer() is ba  # memoized
    assert sub.get_batched_analyzer() is bs
    m.set_analysis({"my": StandardAnalyzer(stopwords=["zap"])})
    assert m.analysis_generation == gen + 1
    # the settings bump cleared BOTH memos, sub-fields included
    assert ft._analyzer_obj is None and ft._batched_obj is None
    assert sub._analyzer_obj is None and sub._batched_obj is None
    ba2, bs2 = ft.get_batched_analyzer(), sub.get_batched_analyzer()
    assert ba2 is not ba and bs2 is not bs
    assert ba2.analyzer is ft.get_analyzer()
    assert "zap" in ba2.analyzer.stopwords
    # a registry analyzer re-resolves to the SAME object after a direct
    # oracle-memo reset, so the batched memo legitimately survives —
    # the identity check keys on the analyzer object, not on None-ness
    ft._analyzer_obj = None
    assert ft.get_batched_analyzer() is ba2
    # ...but a builtin rebuilds a fresh Analyzer instance per resolve,
    # and the identity check must catch that too
    m2 = Mappings({"properties": {"b": {"type": "text"}}})
    ft2 = m2.fields["b"]
    bb = ft2.get_batched_analyzer()
    ft2._analyzer_obj = None
    bb2 = ft2.get_batched_analyzer()
    assert bb2 is not bb and bb2.analyzer is ft2.get_analyzer()


# ---------------------------------------------------------------------------
# shards built at once (the concurrent build that replaced the depth-1
# analyze/build double buffer)
# ---------------------------------------------------------------------------

def test_overlap_pipeline_same_packs_and_worker_spans(monkeypatch):
    monkeypatch.setenv("ES_TPU_ANALYZE", "batched")
    docs = [(str(i), {"body": f"alpha w{i % 7} common text body {i}"})
            for i in range(150)]
    m = Mappings({"properties": {"body": {"type": "text"}}})
    with collect_build_stages() as c:
        sp = build_stacked_pack_routed(route_docs(docs, 3), m,
                                       shard_builders=3)
    assert sp.S == 3
    assert sum(p.num_docs for p in sp.shards) == len(docs)
    # every shard analyzed and built on a worker thread: one span a shard,
    # the workers' own stages as async charges, and the main-thread
    # flat-sum invariant untouched (workers never write `stages`)
    assert c.async_stages.get("build.analyze", 0.0) > 0.0
    assert [n for n, _s, _e in c.async_events] == ["refresh.shard_build"] * 3
    assert all(e >= s for _n, s, e in c.async_events)
    assert "build.analyze" not in c.stages and c.stages["build"] > 0.0
    wall, stages = c.finish()
    assert sum(stages.values()) == pytest.approx(wall)
    # the serial build (one builder) produces the same packs, on the
    # calling thread: its stages charge the collector itself
    with collect_build_stages() as c1:
        sp2 = build_stacked_pack_routed(route_docs(docs, 3), m,
                                        shard_builders=1)
    assert not c1.async_events and c1.stages["build.analyze"] > 0.0
    assert [p.num_docs for p in sp.shards] == [p.num_docs
                                               for p in sp2.shards]
    assert sp.field_stats == sp2.field_stats
    assert np.array_equal(sp.post_docids, sp2.post_docids)
    assert np.array_equal(sp.post_tfs, sp2.post_tfs)


@pytest.mark.parametrize("shard_builders", [3, 1])
def test_overlap_worker_exception_propagates(monkeypatch, shard_builders):
    monkeypatch.setenv("ES_TPU_ANALYZE", "batched")
    docs = [(str(i), {"body": f"w{i}"}) for i in range(40)]
    m = Mappings({"properties": {"body": {"type": "text"}}})

    boom = RuntimeError("analyze worker exploded")
    orig = PackBuilder.add_documents_batch
    calls = {"n": 0}

    def bad(self, parsed_docs, doc_ids=None):
        calls["n"] += 1
        if calls["n"] == 2:  # one shard's builder, whichever thread has it
            raise boom
        return orig(self, parsed_docs, doc_ids=doc_ids)

    monkeypatch.setattr(PackBuilder, "add_documents_batch", bad)
    with pytest.raises(RuntimeError, match="analyze worker exploded"):
        build_stacked_pack_routed(route_docs(docs, 3), m,
                                  shard_builders=shard_builders)


def test_engine_refresh_shows_overlap_in_profile(monkeypatch):
    """End-to-end: a 3-shard engine refresh in batched mode leaves
    worker `build.analyze` spans in the RefreshProfile timestamps
    (stage_events_ms rows tagged worker + async_stages_ms), the
    cumulative recorder accounting sees the worker millis, and
    search results agree with the host-oracle lane."""
    results = {}
    for mode in ("host", "batched"):
        monkeypatch.setenv("ES_TPU_ANALYZE", mode)
        e = Engine(None)
        try:
            e.create_index(
                "t", {"properties": {"body": {"type": "text"}}},
                settings={"number_of_shards": 3})
            idx = e.indices["t"]
            for i, t in enumerate(TEXTS * 6):
                idx.index_doc(f"d{i}", {"body": t or "pad"})
            idx.refresh()
            r = idx.search(
                query={"match_phrase": {"body": "quick brown fox"}},
                size=20)
            results[mode] = [(h["_id"], h["_score"])
                             for h in r["hits"]["hits"]]
            if mode == "batched":
                profs = e.refresh_recorder.profiles()["profiles"]
                prof = next(p for p in profs
                            if p.get("async_stages_ms"))
                assert prof["async_stages_ms"]["build.analyze"] > 0
                tags = {row[3] for row in prof["stage_events_ms"]}
                assert tags == {"main", "worker"}
                assert "analyze_overlap_ms" in prof
                # cumulative accounting folds worker millis in
                st = e.refresh_recorder.indexing_stats()["stage_ms"]
                assert st.get("build.analyze", 0.0) > 0
        finally:
            e.close()
    assert results["host"] and results["host"] == results["batched"]


# ---------------------------------------------------------------------------
# slo.write.analyze_fraction + health remedy
# ---------------------------------------------------------------------------

def test_slo_analyze_fraction_objective_and_health_remedy(monkeypatch):
    monkeypatch.setenv("ES_TPU_ANALYZE", "host")
    e = Engine(None)
    try:
        e.settings.update({"persistent": {
            "slo.write.analyze_fraction": 1e-9}})
        e.create_index("t", {"properties": {"body": {"type": "text"}}})
        idx = e.indices["t"]
        for i in range(120):
            idx.index_doc(str(i), {"body": f"alpha w{i % 37} common"})
        idx.refresh()
        # make analyze the dominant cumulative stage so the health
        # diagnosis exercises the PR-16 remedy branch
        e.refresh_recorder.record(
            {"kind": "full", "docs": 0,
             "stages_ms": {"analyze": 60_000.0}})
        ev = e.slo.evaluate()
        objs = {o["id"]: o for o in ev["objectives"]}
        assert "write-analyze-fraction" in objs
        assert objs["write-analyze-fraction"]["kind"] == "write"
        assert 0 < objs["write-analyze-fraction"]["measured"] <= 1
        assert "write-analyze-fraction" in ev["breached"]
        ind = xpack.health_report(e)["indicators"]["indexing"]
        assert ind["status"] == "yellow"
        assert ind["details"]["dominant_stage"] == "analyze"
        assert "ES_TPU_ANALYZE" in ind["diagnosis"][0]["cause"]
    finally:
        e.close()


def test_slo_analyze_fraction_absent_when_unset():
    e = Engine(None)
    try:
        e.create_index("t", {"properties": {"body": {"type": "text"}}})
        idx = e.indices["t"]
        idx.index_doc("1", {"body": "alpha"})
        idx.refresh()
        ev = e.slo.evaluate()
        assert "write-analyze-fraction" not in {
            o["id"] for o in ev["objectives"]}
    finally:
        e.close()


# ---------------------------------------------------------------------------
# burst-level invariants
# ---------------------------------------------------------------------------

def test_analyze_burst_chains_multivalue_positions(monkeypatch):
    monkeypatch.setenv("ES_TPU_ANALYZE", "batched")
    ba = BatchedAnalyzer(StandardAnalyzer())
    # doc0: ["a b", "c"], doc1: ["d"] — value gap +100 inside doc0 only
    burst = analyze_burst(ba, ["a b", "c", "d"],
                          np.array([0, 0, 1]), 2, mode="batched")
    assert list(burst.terms) == ["a", "b", "c", "d"]
    assert burst.doc_idx.tolist() == [0, 0, 0, 1]
    # "c" starts at last_pos(0)+1+100 = 102; "d" restarts at 0
    assert burst.positions.tolist() == [0, 1, 102, 0]
    assert burst.lengths.tolist() == [3, 1]


def test_analyze_burst_empty_and_zero_token_docs():
    ba = BatchedAnalyzer(StandardAnalyzer())
    burst = analyze_burst(ba, ["", "   "], np.array([0, 1]), 3,
                          mode="batched")
    assert burst.terms.size == 0
    assert burst.lengths.tolist() == [0, 0, 0]
    empty = analyze_burst(ba, [], np.empty(0, np.int64), 0,
                          mode="batched")
    assert empty.terms.size == 0 and empty.lengths.size == 0
