"""One host-to-device transfer a search (PR 27): `parallel/param_pack.py`
and the one place that unpacks, `StackedSearcher._compiled`'s `search_solo`.

(a) pack -> unpack under `jax.jit` gives every leaf back bit for bit;
(b) a search answers exactly what the same program answers when it is handed
    its parameters leaf by leaf, the way it was before this PR;
(c) a search hands its program one host array per dtype class;
(d) the layout belongs to the program's identity.
"""

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

from elasticsearch_tpu import telemetry
from elasticsearch_tpu.index.mappings import Mappings
from elasticsearch_tpu.parallel import sharded
from elasticsearch_tpu.parallel.param_pack import pack, packed_counts, unpack
from elasticsearch_tpu.parallel.sharded import (StackedSearcher,
                                                _stack_shard_params)
from elasticsearch_tpu.parallel.stacked import build_stacked_pack
from elasticsearch_tpu.utils.jax_env import ensure_x64

ensure_x64()


def _bits(x) -> np.ndarray:
    """An array as unsigned words of its own width: equal bits, equal array,
    whatever a NaN carries."""
    a = np.asarray(x)
    if a.dtype == bool:
        return a
    return a.view({2: np.uint16, 4: np.uint32, 8: np.uint64}[a.dtype.itemsize])


def _same_tree(got, want):
    got_leaves, got_def = jtu.tree_flatten(got)
    want_leaves, want_def = jtu.tree_flatten(want)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        g = np.asarray(g)
        assert g.dtype == np.asarray(w).dtype
        assert g.shape == np.shape(w)
        assert np.array_equal(_bits(g), _bits(w))


# -- (a) the round trip -------------------------------------------------------

NAN_WITH_PAYLOAD = np.array([0x7FC12345], np.uint32).view(np.float32)[0]


def _int32(s):
    return {"rows": np.arange(s * 8, dtype=np.int32).reshape(s, 8) - 5,
            "dr": np.full((s,), 2**31 - 1, np.int32)}


def _float32(s):
    special = np.array([-0.0, np.inf, -np.inf, NAN_WITH_PAYLOAD, 1e-45, 3.5],
                       np.float32)
    return (np.tile(special, (s, 1)), np.full((s,), -0.0, np.float32))


def _mixed_words(s):
    # what a `match` plans: rows, weight, avgdl, wscale a term, and a boost
    term = (np.arange(s * 4, dtype=np.int32).reshape(s, 4),
            np.full((s,), 1.25, np.float32), np.full((s,), 56.0, np.float32),
            np.full((s,), NAN_WITH_PAYLOAD, np.float32))
    return ((term, term), np.ones((s,), np.float32))


def _int64(s):
    return (np.full((s, 3), np.iinfo(np.int64).min + 1, np.int64),
            np.full((s,), 2**40 + 7, np.int64))


def _bools(s):
    return [np.array([[True, False, True]] * s), np.zeros((s,), bool)]


def _every_class(s):
    return {"w": _mixed_words(s), "l": _int64(s), "b": _bools(s),
            "u": np.full((s, 2), 2**32 - 1, np.uint32),
            "h": np.full((s, 2), 1.5, np.float16)}


def _scalars(s):
    # 0-d leaves of one shard's `prepare`, stacked to [S]
    one = (np.int32(7), np.float32(-0.0), np.asarray(True),
           np.asarray(-3, np.int64))
    return _stack_shard_params([one] * s)


def _ragged(s):
    # block rows of unequal length a shard: `_stack_shard_params` pads them
    return _stack_shard_params(
        [(np.arange(1 + 3 * i, dtype=np.int32) + 1, np.float32(i))
         for i in range(s)])


def _wide(s):
    return (np.arange(s * 2 * 3 * 4, dtype=np.float32).reshape(s, 2, 3, 4),
            np.zeros((s, 0), np.int32), np.arange(s, dtype=np.int32))


def _empty(s):
    return ((), {})


TREES = {"int32": _int32, "float32": _float32, "mixed_words": _mixed_words,
         "int64": _int64, "bool": _bools, "every_class": _every_class,
         "scalars": _scalars, "ragged": _ragged, "wide": _wide,
         "empty": _empty}


@pytest.mark.parametrize("s", [1, 4])
@pytest.mark.parametrize("kind", sorted(TREES))
def test_pack_then_unpack_under_jit_gives_back_every_bit(kind, s):
    tree = TREES[kind](s)
    buffers, layout = pack(tree)
    hash(layout)
    got = jax.jit(lambda b: unpack(b, layout))(buffers)
    _same_tree(got, tree)
    # one buffer a dtype class, every four-byte number in the int32 one
    dtypes = [b.dtype for b in buffers]
    assert len(set(dtypes)) == len(dtypes)
    leaves = jtu.tree_leaves(tree)
    assert packed_counts(layout) == (len(buffers), len(leaves))
    assert all(b.shape[0] == s and b.ndim == 2 for b in buffers)
    if leaves and all(np.asarray(x).dtype.itemsize == 4 for x in leaves):
        assert dtypes == [np.dtype(np.int32)]
    if not leaves:
        assert buffers == ()


@pytest.mark.parametrize("s", [1, 4])
def test_a_leaf_already_on_the_device_passes_through_beside_the_buffers(s):
    on_device = jnp.arange(s * 3, dtype=jnp.float32).reshape(s, 3)
    second = jnp.ones((s,), jnp.int32)
    tree = {"a": np.full((s, 2), 9, np.int32), "d": on_device,
            "z": (second, np.full((s,), 0.5, np.float32))}
    buffers, layout = pack(tree)
    assert [type(b) for b in buffers[:1]] == [np.ndarray]
    assert buffers[-1] is on_device and buffers[-2] is second
    assert packed_counts(layout) == (1, 2)
    _same_tree(jax.jit(lambda b: unpack(b, layout))(buffers), tree)


def test_the_layout_tells_shapes_dtypes_and_trees_apart():
    a = (np.zeros((1, 4), np.int32), np.zeros((1,), np.float32))
    same = (np.ones((1, 4), np.int32), np.full((1,), 2.0, np.float32))
    assert pack(a)[1] == pack(same)[1]
    assert hash(pack(a)[1]) == hash(pack(same)[1])
    others = [
        (np.zeros((1, 8), np.int32), np.zeros((1,), np.float32)),   # shape
        (np.zeros((1, 4), np.float32), np.zeros((1,), np.float32)),  # dtype
        (np.zeros((1, 2, 2), np.int32), np.zeros((1,), np.float32)),
        [np.zeros((1, 4), np.int32), np.zeros((1,), np.float32)],   # tree
        (np.zeros((1,), np.float32), np.zeros((1, 4), np.int32)),   # order
    ]
    for other in others:
        assert pack(other)[1] != pack(a)[1]


def test_an_unstacked_scalar_is_refused():
    with pytest.raises(ValueError):
        pack((np.float32(1.0),))


# -- (b) the same answers, bit for bit ---------------------------------------

MAPPING = Mappings({"properties": {
    "body": {"type": "text"},
    "status": {"type": "keyword"},
    "host": {"type": "keyword"},
    "bytes": {"type": "long"},
}})
COMMON = ["the", "of", "and", "to", "in"]            # in most docs: dense tier
RARE = [f"w{i}" for i in range(160)]                 # sparse: impact tier
N_DOCS = 1500


def _docs():
    rng = np.random.default_rng(27)
    docs = []
    for i in range(N_DOCS):
        words = [w for w in COMMON if rng.random() < 0.8]
        words += list(rng.choice(RARE, size=rng.integers(2, 9)))
        docs.append((f"d{i}", {
            "body": " ".join(words),
            "status": ["200", "404", "500"][int(rng.integers(0, 3))],
            "host": f"h{int(rng.integers(0, 40))}",
            "bytes": int(rng.integers(1, 5000)),
        }))
    return docs


@pytest.fixture(scope="module", params=[1, 4], ids=["S1", "S4"])
def searcher(request):
    from elasticsearch_tpu.aggs import nodes as agg_nodes

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("ES_TPU_IMPACT", "force")
        mp.setenv("ES_TPU_REQUEST_CACHE", "0")
        # the two-pass terms scheme without a 65,536-value vocabulary
        mp.setattr(agg_nodes, "TWO_PASS_MIN_V", 16)
        yield StackedSearcher(
            build_stacked_pack(_docs(), MAPPING, num_shards=request.param))


def _leaf_by_leaf(tree):
    """What `pack` stands in for: every leaf an argument of its own."""
    leaves, treedef = jtu.tree_flatten(tree)
    layout = (treedef, tuple((i - len(leaves), None, None, None)
                             for i in range(len(leaves))))
    return tuple(leaves), layout


MATCH_12 = "the w1 of w2 and w3 to w4 in w5 w6 w7"
TWO_PASS = {"hosts": {"terms": {"field": "host", "size": 5},
                      "aggs": {"b": {"sum": {"field": "bytes"}}}}}
REQUESTS = {
    "match_1_dense": dict(query={"match": {"body": "the"}}),
    "match_1_impact": dict(query={"match": {"body": "w3"}}),
    "match_5_mixed": dict(query={"match": {"body": "the w1 of w2 w9"}}),
    "match_12_mixed": dict(query={"match": {"body": MATCH_12}}, size=20),
    "bool_range_filter": dict(query={"bool": {
        "must": [{"match": {"body": "w4 and w8"}}],
        "filter": [{"range": {"bytes": {"gte": 100, "lt": 3000}}}]}}),
    "terms": dict(query={"terms": {"status": ["404", "500"]}}, size=15),
    "match_none": dict(query={"match_none": {}}),
    "terms_agg": dict(query={"match": {"body": "w2 the"}}, size=3, aggs={
        "by_status": {"terms": {"field": "status"}}}),
    "two_pass_terms_agg": dict(query={"match": {"body": "of w5"}}, size=2,
                               aggs=TWO_PASS),
}


@pytest.mark.parametrize("name", sorted(REQUESTS))
def test_a_search_answers_what_the_unpacked_program_answers(
        searcher, monkeypatch, name):
    req = REQUESTS[name]
    got = searcher.search(**req)
    programs = set(searcher._cache)
    monkeypatch.setattr(sharded, "pack", _leaf_by_leaf)
    want = searcher.search(**req)
    # the second search ran programs of its own, on the leaves themselves
    fresh = [k for k in searcher._cache if k not in programs]
    assert all(searcher._cache[k].packed == (0, 0) for k in fresh)
    # (a tree without leaves has one layout, however it is handed over)
    assert fresh or name == "match_none"
    if name == "two_pass_terms_agg":
        assert len(fresh) == 2          # pass 1 and the candidates' pass 2
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


def test_the_pool_of_requests_mixes_dense_and_impact_terms(searcher):
    # PR 38: one plan of the match family, its dense list and its impact
    # scored rows both holding real entries
    st = searcher._agg_dispatch(**REQUESTS["match_12_mixed"])
    tag, fld, mode, _td, _tr = st["keys"][0]
    assert (tag, fld, mode) == ("match", "body", "impact"), st["keys"][0]
    rows, dok = st["params"][0], st["params"][5]
    assert np.count_nonzero(rows) and np.count_nonzero(dok)


# -- (c) one host array a dtype class ----------------------------------------

def _dispatch_counters() -> tuple[int, int]:
    c = telemetry.metrics.snapshot()["counters"]
    return (int(c.get("es.search.dispatch.buffers", 0)),
            int(c.get("es.search.dispatch.leaves", 0)))


@pytest.mark.parametrize("name, buffers", [
    ("match_1_impact", 1), ("match_5_mixed", 1), ("match_12_mixed", 1),
    ("bool_range_filter", 3),            # words, the int64 bounds, two bools
    ("terms", 2), ("match_none", 0),
    ("two_pass_terms_agg", None),        # two dispatches
])
def test_a_search_hands_its_program_one_host_array_a_dtype_class(
        searcher, name, buffers):
    req = REQUESTS[name]
    st = searcher._agg_dispatch(**req)
    fn, handed = searcher._packed_program(
        st["node"], st["keys"], st["k"], st["agg_nodes"], st["agg_key"],
        st["params"], st["agg_params"])
    assert all(type(b) is np.ndarray for b in handed)
    dtypes = [b.dtype for b in handed]
    assert len(set(dtypes)) == len(dtypes)
    leaves = len(jtu.tree_leaves((st["params"], st["agg_params"])))
    assert fn.packed == (len(handed), leaves)
    b0, l0 = _dispatch_counters()
    searcher.search(**req)
    b1, l1 = _dispatch_counters()
    if buffers is None:
        # pass 2 packs again: its candidates ride in the same int32 buffer
        assert (len(handed), b1 - b0) == (1, 2)
        assert l1 - l0 == 2 * leaves + 1
    else:
        assert (b1 - b0, l1 - l0) == (buffers, leaves)
        assert len(handed) == buffers


def test_nodes_stats_ships_one_buffer_a_search_of_the_rest_api(monkeypatch):
    """What the benchmark's `engine.dispatch_buffers` and
    `engine.fetch_buffers` read: the counters' rise over the searches
    `rest.search` counted, from `_nodes/stats`."""
    import asyncio

    from elasticsearch_tpu.cache import request_cache

    # the cluster setting below switches the node-wide cache off; a later
    # module in this process (tests/test_spmd.py) needs it as it found it
    monkeypatch.setattr(request_cache(), "_enabled", request_cache()._enabled)
    import json

    from aiohttp.test_utils import TestClient, TestServer

    from elasticsearch_tpu.rest.app import make_app

    async def counters(client):
        nodes = (await (await client.get("/_nodes/stats")).json())["nodes"]
        return next(iter(nodes.values()))["metrics"]["counters"]

    async def go():
        client = TestClient(TestServer(make_app()))
        await client.start_server()
        try:
            await client.put("/_cluster/settings", json={"persistent": {
                "indices.requests.cache.enable": False}})
            await client.put("/i", json={"mappings": {"properties": {
                "body": {"type": "text"}}}})
            lines = []
            for i in range(300):        # past the 256 docs of a tail segment
                lines.append(json.dumps({"index": {"_id": str(i)}}))
                lines.append(json.dumps(
                    {"body": f"{RARE[i % 7]} {RARE[(i + 3) % 7]} the"}))
            r = await client.post(
                "/i/_bulk", data="\n".join(lines) + "\n",
                headers={"Content-Type": "application/x-ndjson"})
            assert r.status == 200, await r.text()
            assert (await client.post("/i/_refresh")).status == 200
            before = await counters(client)
            for text in ("w1", "w2 the w4", "the w0 w1 w2 w3 w4 w5 w6"):
                r = await client.post(
                    "/i/_search", json={"query": {"match": {"body": text}}})
                assert r.status == 200, await r.text()
                assert (await r.json())["hits"]["total"]["value"] > 0
            return before, await counters(client)
        finally:
            engine = client.server.app["engine"]
            if engine._serving is not None:
                engine._serving.stop()
            await client.close()

    before, after = asyncio.run(go())

    def added(key):
        return after[key] - before.get(key, 0)

    assert added("es.span.rest.search.count") == 3
    assert added("es.search.dispatch.buffers") == 3
    assert added("es.search.dispatch.leaves") > 3 * 4
    # and back (PR 32): one fetched buffer a search, four leaves cut from it
    assert added("es.search.fetch.buffers") == 3
    assert added("es.search.fetch.leaves") == 3 * 4


# -- (d) the layout is part of the program's identity ------------------------

def _solo_misses() -> int:
    c = telemetry.metrics.snapshot()["counters"]
    return int(c.get("es.jit.cache.search_solo.misses", 0))


def test_one_plan_key_with_other_terms_shares_its_program(searcher):
    a = searcher._agg_dispatch(query={"match": {"body": "the w1 of w2 w9"}})
    misses = _solo_misses()
    programs = len(searcher._cache)
    b = searcher._agg_dispatch(query={"match": {"body": "and w1 to w2 w9"}})
    assert a["keys"] == b["keys"]
    assert _solo_misses() == misses and len(searcher._cache) == programs
    assert not np.array_equal(
        jax.device_get(a["outs"][0]), jax.device_get(b["outs"][0]))


def test_two_layouts_never_share_a_program(searcher):
    st = searcher._agg_dispatch(query={"match": {"body": "w1 w2"}})
    tree = (st["params"], st["agg_params"])
    _, layout = pack(tree)
    wider = jtu.tree_map(
        lambda x: np.concatenate([x, x], axis=1) if x.ndim == 2 else x, tree)
    _, other = pack(wider)
    assert layout != other
    args = (st["node"], st["keys"], st["k"], None, ())
    fn = searcher._compiled(*args, layout)
    assert searcher._compiled(*args, layout) is fn
    misses = _solo_misses()
    assert searcher._compiled(*args, other) is not fn
    assert _solo_misses() == misses + 1
