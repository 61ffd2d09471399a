"""The benchmark's own arithmetic, on the CPU and without a server:
percentiles and rates, the generator, the reference, the comparison and its
control, and the trace reduction."""

import json
import math
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import control  # noqa: E402
import loop  # noqa: E402
from benchlib import compare as cmp  # noqa: E402
from benchlib import corpus as gen  # noqa: E402
from benchlib import stats, trace  # noqa: E402
from benchlib.reference import Reference, to_bf16  # noqa: E402
from benchlib.stats import Request  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CORPUS = {"generator": "zipf_text", "vocab": 300, "zipf_s": 1.0,
          "doc_len_mean": 40, "doc_len_sd": 18, "doc_len_min": 4}
QUERY = {"from": "documents",
         "terms_share": {"1": 10, "2": 25, "3": 30, "4": 20, "6": 15}}
LIMITS = {"total_wrong": 0, "rank_gap": 2e-4, "score_gap": 2e-4,
          "order_wrong": 0, "repeat_diff": 0}


# -- percentiles and rates ---------------------------------------------------

@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (95, 4.8), (100, 5.0),
                                    (25, 2.0)])
def test_percentile_interpolates_between_closest_ranks(q, want):
    assert stats.percentile([5.0, 1.0, 3.0, 2.0, 4.0], q) == pytest.approx(want)
    assert stats.percentile([5.0, 1.0, 3.0, 2.0, 4.0], q) == pytest.approx(
        float(np.percentile([5.0, 1.0, 3.0, 2.0, 4.0], q)))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def _window_with_a_stall():
    """99 requests of 10 ms back to back, then one that waits 2 s, in a 4 s
    window; one more is shed (429) and one is sent just before the close
    and answered after it."""
    reqs, t = [], 0.0
    for i in range(99):
        reqs.append(Request(i, t, t + 0.010, 200, 9.0))
        t += 0.010
    reqs.append(Request(99, t, t + 2.0, 200, 1999.0))
    reqs.append(Request(100, 3.0, 3.001, 429))
    reqs.append(Request(101, 3.995, 4.005, 200, 9.0))
    return reqs


def test_window_summary_counts_the_stall_and_the_failure():
    s = stats.window_summary(_window_with_a_stall(), 4.0)
    assert s["attempted"] == 102 and s["failed"] == 1
    # answered 200 before the close: 100; the late one counts in latency only
    assert s["answered_in_window"] == 100
    assert s["qps"] == pytest.approx(25.0)
    assert s["p50_ms"] == pytest.approx(10.0)
    # 101 latencies: the stall is the tail, not the median
    assert s["p95_ms"] == pytest.approx(10.0)
    assert s["mean_ms"] == pytest.approx((100 * 10.0 + 2000.0) / 101)
    assert stats.percentile([r.latency_ms for r in _window_with_a_stall()
                             if r.ok], 100) == pytest.approx(2000.0)


def test_a_window_without_answers_has_a_rate_of_zero_and_no_latency():
    s = stats.window_summary([Request(0, 0.0, 0.1, 0)], 1.0)
    assert s["failed"] == 1 and s["qps"] == 0.0 and "p50_ms" not in s


# -- the spread of a set of runs (README.md, Bounds) --------------------------

@pytest.mark.parametrize("values, plain, trimmed", [
    # quartiles as statistics.quantiles(n=4) gives them: 2.25 and 6.75 of
    # 1..8 (NumPy's 2.75 and 6.25 lie closer together); without the 1, 3 and 7
    ([1, 2, 3, 4, 5, 6, 7, 8], 4.5 / 4.5, 4.0 / 5.0),
    # six runs, one far off: 99.375 and 105.75 around 100.25; the 120 left
    # out, 99.25 and 100.75 around 100
    ([100.0, 100.5, 101.0, 99.5, 99.0, 120.0], 6.375 / 100.25, 0.015),
    ([10.0, 10.0, 10.0, 10.0, 10.0, 10.0], 0.0, 0.0),
    # no run is left out of three: two are no set
    ([3.0, 1.0, 2.0], 2.0 / 2.0, 2.0 / 2.0),
])
def test_spread_is_the_quartiles_distance_over_the_median(values, plain, trimmed):
    assert stats.spread(values) == pytest.approx(plain, rel=1e-3)
    assert stats.spread(values, trimmed=True) == pytest.approx(trimmed, rel=1e-3)
    assert stats.spread(values, trimmed=True) <= stats.spread(values)


def test_spread_of_one_run_is_an_error():
    with pytest.raises(ValueError):
        stats.spread([1.0])


def test_loops_summary_takes_plain_runs_by_cell_and_metric():
    def rec(run, p50, qps=None):
        m = {"search_p50_ms": {"value": p50, "unit": "ms"}}
        if qps:
            m["search_qps"] = {"value": qps, "unit": "req/s"}
        return {"run": run, "rc": 0, "wall_s": 1.0, "result": {"metrics": m}}

    lines = loop.summary([
        rec("a.c8:1:0", 20.0, 400.0), rec("a.c8:2:0", 21.0, 380.0),
        rec("a.c8:3:1", 99.0),                      # traced: no end-to-end line
        rec("a.c8:4:0", 19.0, 420.0), rec("b.c1:5:0", 3.0),
        {"run": "a.c8:6:0", "rc": 1, "wall_s": 1.0, "result": {}}])
    assert lines == [
        "a.c8 search_p50_ms: n=3 median=20 spread=0.1000 trimmed=0.1000",
        "a.c8 search_qps: n=3 median=400 spread=0.1000 trimmed=0.1000"]


# -- the generator -----------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 3_000_000_017])
def test_the_same_seed_gives_the_same_corpus_and_pool(seed):
    a = gen.build_corpus(seed, 500, CORPUS)
    b = gen.build_corpus(seed, 500, CORPUS)
    assert np.array_equal(a.lens, b.lens) and np.array_equal(a.tok, b.tok)
    assert gen.build_pool(seed, a, QUERY, 64) == gen.build_pool(seed, b, QUERY, 64)


def test_seeds_differ_in_documents_and_queries_but_not_in_sizes():
    a = gen.build_corpus(1, 500, CORPUS)
    b = gen.build_corpus(2, 500, CORPUS)
    assert not np.array_equal(a.tok[:200], b.tok[:200])
    pa, pb = gen.build_pool(1, a, QUERY, 64), gen.build_pool(2, b, QUERY, 64)
    assert pa != pb
    # the pool's size never changes the corpus, nor the corpus the pool's sizes
    assert np.array_equal(gen.build_corpus(1, 500, CORPUS).tok, a.tok)
    assert all(1 <= len(q) <= 6 and len(set(q)) == len(q) for q in pa + pb)


def test_lengths_have_the_mean_and_spread_asked_for_and_shares_are_kept():
    rng = np.random.default_rng(5)
    wide = gen.doc_lengths(rng, 200_000, 56.0, 25.0, 4)
    assert wide.mean() == pytest.approx(56.0, rel=0.01)
    assert wide.std() == pytest.approx(25.0, rel=0.02) and wide.min() >= 4
    with pytest.raises(ValueError):
        gen.doc_lengths(rng, 10, 40.0, 6.0, 4)   # narrower than a Poisson's 6.3
    # largest remainder: 64 words counts in the shares 10:25:30:20:15
    counts = gen.term_counts(QUERY["terms_share"], 64)
    assert np.bincount(counts).tolist() == [0, 6, 16, 19, 13, 0, 10]
    with pytest.raises(ValueError):
        gen.term_counts({"0": 1, "2": 1}, 8)


def test_negative_seed_and_unknown_generator_are_errors():
    with pytest.raises(ValueError):
        gen.build_corpus(-1, 10, CORPUS)
    with pytest.raises(ValueError):
        gen.build_corpus(1, 10, dict(CORPUS, generator="other"))


def test_bulk_payload_spells_documents_in_id_order():
    c = gen.build_corpus(3, 20, CORPUS)
    words = np.array([f"t{i}" for i in range(c.vocab)])
    lines = gen.bulk_payload(c, "body", 5, 8, words).decode().splitlines()
    assert [json.loads(x)["index"]["_id"] for x in lines[0::2]] == ["5", "6", "7"]
    doc6 = json.loads(lines[3])["body"].split()
    assert doc6 == [f"t{t}" for t in c.tok[c.starts[6]:c.starts[6] + c.lens[6]]]


# -- the reference -----------------------------------------------------------

def _three_docs():
    # doc 0: a a b      doc 1: a c c c      doc 2: b b b b b (ids a=0 b=1 c=2)
    lens = np.array([3, 4, 5])
    tok = np.array([0, 0, 1, 0, 2, 2, 2, 1, 1, 1, 1, 1])
    return lens, tok


def _bm25(tf, df, dl, n=3, avgdl=4.0, k1=1.2, b=0.75):
    idf = math.log(1 + (n - df + 0.5) / (df + 0.5))
    return idf * tf / (tf + k1 * (1 - b + b * dl / avgdl))


def test_reference_against_a_hand_computed_three_document_bm25():
    lens, tok = _three_docs()
    ref = Reference(lens, tok)
    ref.prepare({0, 1, 2})
    assert ref.avgdl == 4.0 and [ref.df(t) for t in (0, 1, 2)] == [2, 2, 1]
    # query "a b": doc0 has both, doc1 has a, doc2 has b
    want = {0: _bm25(2, 2, 3) + _bm25(1, 2, 3), 1: _bm25(1, 2, 4),
            2: _bm25(5, 2, 5)}
    sc = ref.scores([0, 1])
    for d, s in want.items():
        assert sc[d] == pytest.approx(s, rel=1e-12)
    ids, scores, total = ref.top([0, 1], k=2)
    assert total == 3
    assert ids == sorted(want, key=lambda d: -want[d])[:2]
    assert scores == pytest.approx([want[d] for d in ids])
    assert ref.top([2], k=10) == ([1], [pytest.approx(_bm25(3, 1, 4))], 1)


def test_reference_breaks_ties_by_document_and_reports_no_hits():
    lens = np.array([4, 4, 4, 4])
    tok = np.array([0, 1, 1, 1,  0, 2, 2, 2,  3, 3, 3, 3,  0, 1, 2, 3])
    ref = Reference(lens, tok)
    ref.prepare({0, 5})
    assert ref.top([0], k=2)[0] == [0, 1]      # three documents tie: doc asc
    assert ref.top([0], k=10)[2] == 3
    assert ref.top([5]) == ([], [], 0)


def test_bf16_rounding_is_to_nearest_even():
    assert to_bf16(np.array([1.0, 1.00390625, 1.01171875, 3.14159])).tolist() == [
        1.0, 1.0, 1.015625, 3.140625]


# -- the comparison and its control -----------------------------------------

@pytest.fixture(scope="module")
def small():
    c = gen.build_corpus(5, 3000, dict(CORPUS, vocab=2000))
    pool = gen.build_pool(5, c, QUERY, 96)
    ref = Reference(c.lens, c.tok)
    served = control.answers_of(Reference(c.lens, c.tok), pool,
                                list(range(96)), 10)
    return c, pool, ref, served


def _verdict(small, served):
    c, pool, ref, _ = small
    return cmp.compare(ref, pool, served, list(range(96)), 10, LIMITS)


def test_the_reference_in_the_programs_place_is_correct(small):
    v = _verdict(small, small[3])
    assert v["correct"] and v["compared"] == 96
    assert all(n["value"] == 0 for n in v["numbers"].values())
    assert v["numbers"]["ids_differ"]["limit"] is None   # reported, not judged


def _copy(r, **kw):
    d = dict(query=r.query, sent=r.sent, done=r.done, status=r.status,
             took_ms=r.took_ms, ids=list(r.ids), scores=list(r.scores),
             total=dict(r.total))
    d.update(kw)
    return Request(**d)


def _full(small):
    return next(i for i, r in enumerate(small[3]) if len(r.ids) == 10
                and r.scores[0] > r.scores[9])


@pytest.mark.parametrize("fault,number", [
    ("wrong_doc", "rank_gap"), ("dropped_hit", "rank_gap"),
    ("repeated_hit", "rank_gap"), ("wrong_total", "total_wrong"),
    ("lower_bound_total", "total_wrong"), ("swapped_order", "order_wrong"),
    ("score_off", "score_gap"), ("second_answer_differs", "repeat_diff")])
def test_an_altered_answer_fails_its_number(small, fault, number):
    served = list(small[3])
    i = _full(small)
    r = served[i]
    if fault == "wrong_doc":
        miss = next(d for d in range(3000) if d not in r.ids
                    and small[2].scores(small[1][r.query])[d] == 0)
        served[i] = _copy(r, ids=r.ids[:9] + [miss])
    elif fault == "dropped_hit":
        served[i] = _copy(r, ids=r.ids[:9], scores=r.scores[:9])
    elif fault == "repeated_hit":
        served[i] = _copy(r, ids=r.ids[:9] + [r.ids[0]])
    elif fault == "wrong_total":
        served[i] = _copy(r, total={"value": r.total["value"] + 1, "relation": "eq"})
    elif fault == "lower_bound_total":
        served[i] = _copy(r, total={"value": r.total["value"], "relation": "gte"})
    elif fault == "swapped_order":
        served[i] = _copy(r, ids=r.ids[::-1], scores=r.scores[::-1])
    elif fault == "score_off":
        served[i] = _copy(r, scores=[s * 1.01 for s in r.scores])
    elif fault == "second_answer_differs":
        served.append(_copy(r, ids=r.ids[:9] + [r.ids[9] + 1]))
    v = _verdict(small, served)
    assert not v["correct"]
    n = v["numbers"][number]
    assert n["value"] > n["limit"]


def test_answers_that_failed_are_not_compared_and_none_is_not_correct(small):
    v = _verdict(small, [_copy(r, status=429) for r in small[3]])
    assert v["compared"] == 0 and not v["correct"]


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_the_bf16_control_comes_out_not_correct(seed):
    """The control at a size a test run can hold: the reference in bfloat16 in
    the program's place fails rank_gap or score_gap by a wide margin."""
    spec = {"config": {"documents": 6000, "corpus": dict(CORPUS, vocab=3000),
                       "query": QUERY, "search": {"field": "body", "size": 10},
                       "number_of_shards": 1, "limits": LIMITS},
            "traffic": {"pool": 128, "check_sample": 128}}
    v = control.reference_control(spec, seed)
    assert not v["correct"]
    assert v["numbers"]["total_wrong"]["value"] == 0   # totals stay exact
    assert max(v["numbers"]["rank_gap"]["value"],
               v["numbers"]["score_gap"]["value"]) > 10 * LIMITS["score_gap"]
    assert control.reference_control(spec, seed, precision="f64")["correct"]


def test_the_sample_is_drawn_from_the_seed_and_holds_a_longest_query(small):
    pool = small[1]
    answered = list(range(0, 96, 2))
    a = cmp.draw_sample(9, answered, pool, 16)
    assert a == cmp.draw_sample(9, answered, pool, 16) and len(a) == 16
    assert a != cmp.draw_sample(10, answered, pool, 16)
    assert set(a) <= set(answered)
    assert max(len(pool[q]) for q in a) == max(len(pool[q]) for q in answered)
    assert cmp.draw_sample(9, answered, pool, 500) == answered


# -- the trace reduction -----------------------------------------------------

def test_union_of_intervals():
    assert trace.union_seconds([(0, 1), (0.5, 2), (3, 4), (3.2, 3.5)]) == 3.0
    assert trace.union_seconds([]) == 0.0


@pytest.fixture(scope="module")
def recorded():
    return trace.load(os.path.join(HERE, "recorded_tpu_capture.xplane.pb"))


def test_trace_reduction_on_the_recorded_capture(recorded):
    with open(os.path.join(HERE, "recorded_tpu_capture.expected.json")) as f:
        want = json.load(f)
    got = trace.reduce(recorded)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["span_s"] == pytest.approx(want["span_s"], rel=1e-9)
    assert 0 < got["busy_s"] < got["span_s"]
    assert [n for n, _ in got["device_ops"]] == want["top_ops"]
    assert got["idle_gap_s"] == pytest.approx(got["span_s"] - got["busy_s"],
                                              rel=1e-6)
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10


def test_a_capture_without_a_device_plane_reduces_to_nothing():
    from jax.profiler import ProfileData

    text = '''planes { name: "/host:CPU" lines { name: "python" timestamp_ns: 0
      events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 } }
      event_metadata { key: 1 value { id: 1 name: "work" } } }'''
    assert trace.reduce(ProfileData.from_text_proto(text)) is None


def test_hand_written_device_plane_busy_idle_top_ops_and_gap_names():
    from jax.profiler import ProfileData

    # device: op A 0-2 ms, op B 1-3 ms (overlap), gap 3-7 ms, op A 7-8 ms
    # module line covers the same time again and must not be counted
    text = '''
    planes { name: "/device:TPU:0"
      lines { name: "XLA Ops" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000 }
        events { metadata_id: 2 offset_ps: 1000000000 duration_ps: 2000000000 }
        events { metadata_id: 1 offset_ps: 7000000000 duration_ps: 1000000000 } }
      lines { name: "XLA Modules" timestamp_ns: 1000
        events { metadata_id: 3 offset_ps: 0 duration_ps: 8000000000 } }
      event_metadata { key: 1 value { id: 1 name: "fusion.A" } }
      event_metadata { key: 2 value { id: 2 name: "fusion.B" } }
      event_metadata { key: 3 value { id: 3 name: "jit(run)" } } }
    planes { name: "/host:CPU"
      lines { name: "engine" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 0 duration_ps: 8000000000 }
        events { metadata_id: 2 offset_ps: 3100000000 duration_ps: 3800000000 } }
      event_metadata { key: 1 value { id: 1 name: "serve" } }
      event_metadata { key: 2 value { id: 2 name: "plan" } } }'''
    got = trace.reduce(ProfileData.from_text_proto(text))
    assert got["busy_s"] == pytest.approx(4e-3)
    assert got["span_s"] == pytest.approx(8e-3)
    assert got["device_ops"] == [["fusion.A", pytest.approx(3e-3)],
                                 ["fusion.B", pytest.approx(2e-3)]]
    # the innermost host span that covers the gap names it
    assert got["idle_gaps"] == [["engine: plan", pytest.approx(4e-3)]]


FOUR_PLANES = '''
planes { name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 1000000000 }
    events { metadata_id: 2 offset_ps: 2000000000 duration_ps: 1000000000 }
    events { metadata_id: 1 offset_ps: 6000000000 duration_ps: 2000000000 } }
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 1000000000 duration_ps: 2000000000 }
    events { metadata_id: 3 offset_ps: 6000000000 duration_ps: 2000000000 } }
  event_metadata { key: 1 value { id: 1 name: "%scan = f32[8] custom-call()" } }
  event_metadata { key: 2 value { id: 2 name: "%all-gather.1 = f32[32] all-gather()" } }
  event_metadata { key: 3 value { id: 3 name: "jit_search_solo(5)" } } }
planes { name: "/device:TPU:1"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 2000000000 duration_ps: 1000000000 } }
  event_metadata { key: 1 value { id: 1 name: "%scan = f32[8] custom-call()" } }
  event_metadata { key: 2 value { id: 2 name: "%all-gather.1 = f32[32] all-gather()" } } }
planes { name: "/device:TPU:2"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1500000000 duration_ps: 500000000 }
    events { metadata_id: 2 offset_ps: 2000000000 duration_ps: 1000000000 } }
  event_metadata { key: 1 value { id: 1 name: "%scan = f32[8] custom-call()" } }
  event_metadata { key: 2 value { id: 2 name: "%all-gather.1 = f32[32] all-gather()" } } }
planes { name: "/device:TPU:3"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 1500000000 }
    events { metadata_id: 1 offset_ps: 2000000000 duration_ps: 1000000000 }
    events { metadata_id: 2 offset_ps: 8500000000 duration_ps: 1000000000 } }
  event_metadata { key: 1 value { id: 1 name: "%scan = f32[8] custom-call()" } }
  event_metadata { key: 2 value { id: 2 name: "%all-gather.1 = f32[32] all-gather()" } } }
planes { name: "/device:TPU:0 CUSTOM trace"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000000 } }
  event_metadata { key: 1 value { id: 1 name: "not an operation" } } }
planes { name: "/host:CPU"
  lines { name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 3500000000 duration_ps: 2000000000 } }
  event_metadata { key: 1 value { id: 1 name: "engine.plan" } } }'''


def test_four_device_planes_with_unequal_busy_time():
    """A cell on four chips: each plane's own busy time, their mean, the span
    from the first operation on any plane to the last on any, operations in
    seconds a plane, and the first plane's gaps."""
    from jax.profiler import ProfileData

    got = trace.reduce(ProfileData.from_text_proto(FOUR_PLANES))
    # plane 0: 1-3 and 6-8 ms; plane 1: 0-3; plane 2: 1.5-3; plane 3: 1-3
    # (two operations overlap) and 8.5-9.5
    assert got["per_device_busy_s"] == {
        "/device:TPU:0": pytest.approx(4e-3), "/device:TPU:1": pytest.approx(3e-3),
        "/device:TPU:2": pytest.approx(1.5e-3), "/device:TPU:3": pytest.approx(3e-3)}
    assert got["busy_s"] == pytest.approx((4 + 3 + 1.5 + 3) / 4 * 1e-3)
    assert got["span_s"] == pytest.approx(9.5e-3)     # 0 on plane 1, 9.5 on plane 3
    assert got["device_ops"] == [["scan", pytest.approx(8 / 4 * 1e-3)],
                                 ["all-gather.1", pytest.approx(4 / 4 * 1e-3)]]
    # the first plane idles 3-6 ms; the plan's annotation covers 3.5-5.5
    assert got["idle_gap_s"] == pytest.approx(3e-3)
    assert got["idle_gaps"] == [["engine.plan", pytest.approx(2e-3)],
                                [trace.NO_STAGE, pytest.approx(1e-3)]]
    assert got["device_lead_s"] == 0.0


def _roofline():
    import importlib.util

    path = os.path.join(BENCH, "layer_metrics", "postings_roofline.py")
    spec = importlib.util.spec_from_file_location("pr", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("devices, want", [(1, 1.0), (4, 0.25)])
def test_postings_roofline_least_time(devices, want):
    df = np.array([100, 10, 1])
    # (100 + 10) + (1) postings x 8 bytes at 888 bytes/s a device
    mod = _roofline()
    assert mod.least_seconds(df, [[0, 1], [2]], 888.0, devices) == \
        pytest.approx(want)
    if devices == 1:     # as it was before it took a number of devices
        assert mod.least_seconds(df, [[0, 1], [2]], 888.0) == \
            111 * 8 / 888.0


def test_postings_roofline_on_four_planes_is_bytes_over_four_chips_rate():
    from jax.profiler import ProfileData

    class Run:
        trace = trace.reduce(ProfileData.from_text_proto(FOUR_PLANES))
        peak = {"hbm_bytes_per_s": 819e9}
        pool = [[0, 1], [2]]
        traced = [Request(0, 0, 1, 200), Request(1, 1, 2, 200),
                  Request(1, 1, 2, 503)]

        @staticmethod
        def df():
            return np.array([3_000_000, 500_000, 70_000])

    mean_busy = (4 + 3 + 1.5 + 3) / 4 * 1e-3
    want = 100.0 * (3_570_000 * 8 / (4 * 819e9)) / mean_busy
    assert _roofline().read(Run) == pytest.approx(want, rel=1e-12)
    # one plane of the same capture: one chip's rate over that plane's time
    Run.trace = dict(Run.trace, busy_s=4e-3,
                     per_device_busy_s={"/device:TPU:0": 4e-3})
    assert _roofline().read(Run) == pytest.approx(
        100.0 * (3_570_000 * 8 / 819e9) / 4e-3, rel=1e-12)
