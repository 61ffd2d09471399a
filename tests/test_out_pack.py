"""One device-to-host transfer a search (PR 32): `param_pack.pack_outputs` /
`unpack_host`, the one program that ends in the first (`search_solo`) and the
one helper that calls the second (`StackedSearcher._fetched`).

(a) pack_outputs under `jax.jit` -> `device_get` -> unpack_host gives every
    leaf back bit for bit, as views of the fetched buffers;
(b) a search answers exactly what the same program answers when it hands its
    result tree back leaf by leaf, the way it did before this PR;
(c) a search's fetch pulls one device array per dtype class, counted (through
    REST `_nodes/stats` too: `tests/test_param_pack.py`'s test of both ways);
(d) the benchmark's reader `engine.fetch_buffers` over those counters.
"""

import importlib.util
import os
import types

import jax
import jax.tree_util as jtu
import numpy as np
import pytest

from test_param_pack import MAPPING, REQUESTS, _bits, _docs

from elasticsearch_tpu import telemetry
from elasticsearch_tpu.parallel import sharded
from elasticsearch_tpu.parallel.param_pack import pack_outputs, unpack_host
from elasticsearch_tpu.parallel.sharded import StackedSearcher
from elasticsearch_tpu.parallel.stacked import build_stacked_pack
from elasticsearch_tpu.utils.jax_env import ensure_x64

ensure_x64()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- (a) the round trip -------------------------------------------------------

NAN_WITH_PAYLOAD = np.array([0x7FC12345], np.uint32).view(np.float32)[0]
SPECIAL = np.array([-0.0, np.inf, -np.inf, NAN_WITH_PAYLOAD, 1e-45, 3.5],
                   np.float32)


def _result(k):
    """The tree of a `match`: three rows of k and the total, no aggregation."""
    scores = np.resize(SPECIAL, k)
    return (scores, np.arange(k, dtype=np.int32) % 4,
            np.arange(k, dtype=np.int32)[::-1] * 7, np.int32(2**31 - 1), {})


TREES = {
    "float32": lambda: (SPECIAL, np.float32(-0.0), SPECIAL.reshape(2, 3)),
    "int32": lambda: {"rows": np.arange(24, dtype=np.int32).reshape(2, 3, 4) - 5,
                      "top": np.int32(-(2**31))},
    "uint32": lambda: [np.full((3,), 2**32 - 1, np.uint32), np.uint32(7)],
    "bool": lambda: (np.array([True, False, True]), np.asarray(False)),
    "int64": lambda: (np.full((2, 3), np.iinfo(np.int64).min + 1, np.int64),
                      np.int64(2**40 + 7)),
    "float64": lambda: (np.array([-0.0, np.inf, np.nan, 1e-300]),
                        np.float64(0.1)),
    "every_class": lambda: {
        "w": (SPECIAL, np.arange(5, dtype=np.int32), np.uint32(9)),
        "l": np.arange(4, dtype=np.int64) << 33, "d": np.array([0.1, -0.0]),
        "b": np.array([[True], [False]]), "h": np.full((2,), 1.5, np.float16)},
    "scalars": lambda: (np.int32(7), np.float32(-0.0), np.asarray(True),
                        np.int64(-3)),
    "empty_tree": lambda: ((), {}),
    "empty_leaves": lambda: (np.zeros((0,), np.float32),
                             np.zeros((3, 0), np.int32), np.int32(1)),
    "terms_agg": lambda: (*_result(3)[:4], {"by_status": {
        "counts": np.arange(12, dtype=np.int32).reshape(4, 3),
        "sums": np.arange(12, dtype=np.float64).reshape(4, 3)}}),
    "k1": lambda: _result(1), "k10": lambda: _result(10),
    "k1000": lambda: _result(1000),
}


@pytest.mark.parametrize("kind", sorted(TREES))
def test_pack_outputs_then_unpack_host_gives_back_every_bit(kind):
    tree = TREES[kind]()
    box = {}

    def program(t):
        buffers, box["layout"] = pack_outputs(t)
        return buffers

    fetched = jax.device_get(jax.jit(program)(tree))
    layout = box["layout"]
    hash(layout)
    got = unpack_host(fetched, layout)
    got_leaves, got_def = jtu.tree_flatten(got)
    want_leaves, want_def = jtu.tree_flatten(tree)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        assert type(g) is np.ndarray
        assert g.dtype == np.asarray(w).dtype and g.shape == np.shape(w)
        assert np.array_equal(_bits(g), _bits(w))
        # cut out of what was fetched, not copied
        assert not g.size or any(np.shares_memory(g, b) for b in fetched)
    # one flat buffer a dtype class, every four-byte number in the int32 one
    dtypes = [b.dtype for b in fetched]
    assert len(set(dtypes)) == len(dtypes)
    assert all(b.ndim == 1 for b in fetched)
    assert sum(b.size for b in fetched) == sum(
        np.size(x) for x in want_leaves)
    if want_leaves and all(
            np.asarray(x).dtype.itemsize == 4 for x in want_leaves):
        assert dtypes == [np.dtype(np.int32)]
    if not want_leaves:
        assert fetched == ()
    if kind.startswith("k"):
        k = int(kind[1:])
        assert [b.shape for b in fetched] == [(3 * k + 1,)]


def test_the_device_is_asked_for_no_64_bit_bitcast():
    text = jax.jit(lambda t: pack_outputs(t)[0]).lower(
        TREES["every_class"]()).as_text()
    casts = [ln for ln in text.splitlines() if "bitcast_convert" in ln]
    assert casts and not any("64" in ln for ln in casts), casts


def test_the_output_layout_tells_shapes_dtypes_and_trees_apart():
    def layout_of(tree):
        return pack_outputs(tree)[1]

    a = _result(10)
    assert layout_of(a) == layout_of(_result(10))
    for other in (_result(11), (*a[:3], np.int64(1), {}),
                  (*a[:4], {"x": np.int32(0)}), list(a[:4])):
        assert layout_of(other) != layout_of(a)


# -- (b) the same answers, bit for bit ---------------------------------------

@pytest.fixture(scope="module", params=[(1, "vmap"), (4, "pjit"),
                                        (4, "shardmap")],
                ids=["S1-vmap", "S4-pjit", "S4-shardmap"])
def searcher(request):
    from elasticsearch_tpu.aggs import nodes as agg_nodes
    from elasticsearch_tpu.parallel.spmd import make_mesh

    shards, mode = request.param
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("ES_TPU_IMPACT", "force")
        mp.setenv("ES_TPU_REQUEST_CACHE", "0")
        mp.setenv("ES_TPU_SPMD", mode)
        mp.delenv("ES_TPU_REPLICAS", raising=False)
        # the two-pass terms scheme without a 65,536-value vocabulary
        mp.setattr(agg_nodes, "TWO_PASS_MIN_V", 16)
        mesh = make_mesh(shards) if shards > 1 else None
        assert (mesh is None) == (shards == 1)
        s = StackedSearcher(
            build_stacked_pack(_docs(), MAPPING, num_shards=shards), mesh=mesh)
        assert s._exec == mode
        yield s


def _leaf_by_leaf(tree):
    """What `pack_outputs` stands in for: every leaf an output of its own."""
    leaves, treedef = jtu.tree_flatten(tree)
    return tuple(leaves), (treedef, (None,) * len(leaves))


def _leaves_as_fetched(buffers, layout):
    return jtu.tree_unflatten(layout[0], list(buffers))


@pytest.mark.parametrize("name", sorted(REQUESTS))
def test_a_search_answers_what_the_leaf_by_leaf_program_answers(
        searcher, monkeypatch, name):
    req = REQUESTS[name]
    got = searcher.search(**req)
    st = searcher._agg_dispatch(**req)
    # (c) one device array a dtype class is what the fetch will wait for
    dtypes = [b.dtype for b in st["outs"]]
    assert len(set(dtypes)) == len(dtypes) and all(
        b.ndim == 1 for b in st["outs"])
    if not req.get("aggs"):
        assert [(b.dtype, b.shape) for b in st["outs"]] == [
            (np.dtype(np.int32), (3 * st["k"] + 1,))]
    if searcher.mesh is not None:
        assert all(b.sharding.is_fully_replicated for b in st["outs"])
    # the same body, its tree handed back the way it was before this PR
    monkeypatch.setattr(searcher, "_cache", {})
    monkeypatch.setattr(sharded, "pack_outputs", _leaf_by_leaf)
    monkeypatch.setattr(sharded, "unpack_host", _leaves_as_fetched)
    plain = searcher._agg_dispatch(**req)
    assert len(plain["outs"]) == len(plain["out_layout"][1]) >= 4
    assert plain["outs"][0].dtype == np.float32
    want = searcher.search(**req)
    assert len(searcher._cache) == (2 if name == "two_pass_terms_agg" else 1)
    assert got.total == want.total
    if name != "match_none":
        assert got.total > 0
    for field in ("doc_shards", "doc_ids", "scores"):
        g, w = getattr(got, field), getattr(want, field)
        assert g.dtype == w.dtype and np.array_equal(_bits(g), _bits(w)), field
    assert got.max_score == want.max_score
    assert got.aggregations == want.aggregations
    if req.get("aggs"):
        assert got.aggregations and all(
            a["buckets"] for a in got.aggregations.values())


def test_search_many_and_search_batch_answer_what_search_answers(searcher):
    names = ["match_5_mixed", "two_pass_terms_agg", "match_1_impact",
             "terms_agg"]
    solo = [searcher.search(**REQUESTS[n]) for n in names]
    waves = (searcher.search_batch([dict(REQUESTS[n]) for n in names]),
             searcher.search_many([dict(REQUESTS[n]) for n in names]))
    for wave in waves:
        for got, want in zip(wave, solo):
            assert got.total == want.total
            assert np.array_equal(_bits(got.scores), _bits(want.scores))
            assert np.array_equal(got.doc_ids, want.doc_ids)
            assert got.aggregations == want.aggregations


# -- (c) one device array a dtype class, counted -----------------------------

def _fetch_counters() -> tuple[int, int]:
    c = telemetry.metrics.snapshot()["counters"]
    return (int(c.get("es.search.fetch.buffers", 0)),
            int(c.get("es.search.fetch.leaves", 0)))


@pytest.mark.parametrize("name, fetches", [
    ("match_1_impact", 1), ("match_12_mixed", 1), ("bool_range_filter", 1),
    ("match_none", 1), ("terms_agg", 1),
    ("two_pass_terms_agg", 2),           # pass 2 is fetched on its own
])
def test_a_search_fetches_one_device_array_a_dtype_class(
        searcher, name, fetches):
    req = REQUESTS[name]
    st = searcher._agg_dispatch(**req)
    leaves = len(st["out_layout"][1])
    assert leaves == 4 + len(jtu.tree_leaves(
        unpack_host(jax.device_get(st["outs"]), st["out_layout"])[4]))
    b0, l0 = _fetch_counters()
    searcher.search(**req)
    b1, l1 = _fetch_counters()
    if not req.get("aggs"):
        assert (b1 - b0, l1 - l0) == (1, 4)
    elif fetches == 1:
        assert (b1 - b0, l1 - l0) == (len(st["outs"]), leaves)
    else:
        assert b1 - b0 >= 2 * len(st["outs"]) and l1 - l0 > 2 * 4
    # never a device array a leaf
    assert b1 - b0 < l1 - l0


# -- (d) the benchmark's reader ----------------------------------------------

BUFFERS = "es.search.fetch.buffers"
SEARCHES = "es.span.rest.search.count"


def _read(before: dict, after: dict):
    path = os.path.join(REPO, "benchmark", "layer_metrics",
                        "engine.fetch_buffers.py")
    spec = importlib.util.spec_from_file_location("engine_fetch_buffers", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(types.SimpleNamespace(
        before={"counters": before}, after={"counters": after}))


@pytest.mark.parametrize("before, after, want", [
    # the warm-up's fetches are taken out: 1,500 searches, one buffer each
    ({BUFFERS: 192.0, SEARCHES: 192}, {BUFFERS: 1692.0, SEARCHES: 1692}, 1.0),
    # a window of aggregations with 64-bit sums and a second pass
    ({BUFFERS: 10.0, SEARCHES: 10}, {BUFFERS: 50.0, SEARCHES: 20}, 4.0),
    # counted from nothing
    ({}, {BUFFERS: 7.0, SEARCHES: 4}, 1.75),
    # every search was answered by the request cache: nothing fetched
    ({BUFFERS: 5.0, SEARCHES: 5}, {BUFFERS: 5.0, SEARCHES: 9}, 0.0),
])
def test_fetched_buffers_a_search_over_the_window(before, after, want):
    assert _read(before, after) == pytest.approx(want)


@pytest.mark.parametrize("before, after", [
    # the parent of this PR: the dispatch's counter and no fetch counter
    ({SEARCHES: 192, "es.search.dispatch.buffers": 192.0},
     {SEARCHES: 1692, "es.search.dispatch.buffers": 1692.0}),
    # a server without stage counters either
    ({"es.jit.compiles": 85.0}, {"es.jit.compiles": 85.0}),
    ({}, {}),
    # no search ended in the window
    ({BUFFERS: 5.0, SEARCHES: 5}, {BUFFERS: 5.0, SEARCHES: 5}),
    ({BUFFERS: 5.0}, {BUFFERS: 9.0}),
])
def test_no_fetch_counter_gives_none_and_never_raises(before, after):
    assert _read(before, after) is None


def test_the_metric_is_declared_under_the_fetch_s_layer():
    import json

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "engine.fetch_buffers"]
    assert entry == [{
        "name": "engine.fetch_buffers", "unit": "count", "better": "lower",
        "source": "program_counter",
        "layer": "host planning, dispatch and fetch",
        "moves": "search_p50_ms"}]
