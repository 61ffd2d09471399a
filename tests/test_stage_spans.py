"""The stage spans of a served search (PR 26): one primitive
(`telemetry.TRACER.span` / `record`) that feeds the span tree of
`GET /_trace/{id}`, the `es.span.<stage>.ns` / `.count` counters and, for the
leaves, the profiler capture's annotations; the device programs' names and
scopes; the counters beside them. No test here sleeps or asserts on a
duration: order comes from events, and intervals are compared with each other
on one clock."""

import asyncio
import collections
import importlib.util
import io
import json
import os
import re
import sys
import threading
import time

import pytest

from elasticsearch_tpu import telemetry
from elasticsearch_tpu.telemetry import (ANNOTATED_STAGES, STAGES, TRACER,
                                         WAVE_STAGES)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta"]
# the stages of a search's tree, each with its parent; a search without
# aggregations enters `engine.collect` twice (the result, then its hits)
PARENT = {
    "rest.search": "http POST /{index}/_search",
    "engine.queue": "rest.search", "engine.search": "rest.search",
    "rest.respond": "rest.search",
    "executeQueryPhase": "engine.search",
    "engine.parse": "executeQueryPhase", "engine.plan": "executeQueryPhase",
    "engine.dispatch": "executeQueryPhase",
    "engine.fetch": "executeQueryPhase",
    "engine.collect": "executeQueryPhase",
}


def _stage_counters() -> dict:
    return {k: v for k, v in telemetry.metrics.snapshot()["counters"].items()
            if k.startswith("es.span.")}


def _root_of(trace_id: str):
    return next(r for r in TRACER.finished if r.trace_id == trace_id)


def _walk(span, parent=None):
    yield span, parent
    for child in span.children:
        yield from _walk(child, span)


async def _client_with_index(name="i", docs=300):
    """A served index past the 256 documents an incremental refresh would
    put into a tail segment (a tail is searched by a program of its own)."""
    from aiohttp.test_utils import TestClient, TestServer

    from elasticsearch_tpu.rest.app import make_app

    client = TestClient(TestServer(make_app()))
    await client.start_server()
    await client.put("/_cluster/settings", json={"persistent": {
        "indices.requests.cache.enable": False}})
    r = await client.put(f"/{name}", json={"mappings": {"properties": {
        "body": {"type": "text"}}}})
    assert r.status == 200, await r.text()
    lines = []
    for i in range(docs):
        lines.append(json.dumps({"index": {"_id": str(i)}}))
        lines.append(json.dumps(
            {"body": f"{WORDS[i % 7]} {WORDS[(i + 3) % 7]} common"}))
    r = await client.post(f"/{name}/_bulk", data="\n".join(lines) + "\n",
                          headers={"Content-Type": "application/x-ndjson"})
    assert r.status == 200, await r.text()
    assert (await client.post(f"/{name}/_refresh")).status == 200
    return client


async def _close(client):
    engine = client.server.app["engine"]
    if engine._serving is not None:
        engine._serving.stop()
    await client.close()


async def _search(client, text, index="i"):
    r = await client.post(f"/{index}/_search",
                          json={"query": {"match": {"body": text}}})
    assert r.status == 200, await r.text()
    return r


def test_stage_names_are_fit_for_the_readers():
    """No stage may hold a substring that `benchlib.trace` drops as a frame
    that only waits, and every annotated stage must look like a stage to
    `benchlib.spans`; the benchmark imports nothing of the program, so the
    two lists meet here."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        from benchlib import spans, trace
    finally:
        sys.path.pop(0)
    every = STAGES + WAVE_STAGES
    assert ANNOTATED_STAGES < set(every)
    for name in every:
        assert not [w for w in trace.WAITING if w in name], name
        assert spans.STAGE.match(name), name
    assert len(set(every)) == len(every)


def test_one_search_yields_one_tree_of_stages_and_moves_their_counters():
    async def go():
        client = await _client_with_index()
        try:
            await _search(client, "alpha gamma")      # compiles the shape
            before = _stage_counters()
            r = await _search(client, "beta delta")
            after = _stage_counters()
            trace_id = r.headers["X-Trace-Id"]
            tree = await (await client.get(f"/_trace/{trace_id}")).json()
            return before, after, _root_of(trace_id), tree
        finally:
            await _close(client)

    before, after, root, tree = asyncio.run(go())
    spans = list(_walk(root))
    names = collections.Counter(s.name for s, _ in spans)
    assert names == {"http POST /{index}/_search": 1, "executeQueryPhase": 1,
                     **{n: 1 for n in STAGES}, "engine.collect": 2}
    assert {s.trace_id for s, _ in spans} == {root.trace_id}
    assert len({s.span_id for s, _ in spans}) == len(spans)
    for s, parent in spans:
        if parent is None:
            continue
        assert parent.name == PARENT[s.name], (s.name, parent.name)
        assert s.parent_span_id == parent.span_id
        # inside the parent's interval, both on time.perf_counter's clock
        assert parent.start <= s.start <= s.end <= parent.end, s.name
    plan = next(s for s, _ in spans if s.name == "engine.plan")
    # PR 38: a match's plan names its program's tiers in the family
    at = plan.attributes
    assert set(at) == {"program_cache", "dense_tier", "rows_tier",
                       "padded_rows"}
    assert at["program_cache"] == "hit"
    assert at["padded_rows"] == at["dense_tier"] + at["rows_tier"]
    for name in STAGES:
        n = 2 if name == "engine.collect" else 1
        assert (after[f"es.span.{name}.count"]
                - before[f"es.span.{name}.count"]) == n
        assert after[f"es.span.{name}.ns"] > before[f"es.span.{name}.ns"]
        assert isinstance(after[f"es.span.{name}.ns"], int)
    # the same tree, by the id the response gave, from GET /_trace/{id}
    assert tree["span_count"] == len(spans)
    assert [s["name"] for s in tree["spans"]] == ["http POST /{index}/_search"]


def test_a_search_behind_a_held_engine_thread_reads_the_hold_as_queue():
    async def go():
        client = await _client_with_index()
        pool = client.server.app["pool"]
        holding, release = threading.Event(), threading.Event()
        try:
            await _search(client, "alpha gamma")
            held = pool.submit(lambda: (holding.set(), release.wait(60)))
            loop = asyncio.get_running_loop()
            assert await loop.run_in_executor(None, holding.wait, 60)
            task = asyncio.ensure_future(_search(client, "beta delta"))
            while pool._work_queue.qsize() == 0:    # handed to the pool
                await asyncio.sleep(0)
            t0 = time.perf_counter()
            for _ in range(50):
                await asyncio.sleep(0)
            t1 = time.perf_counter()
            release.set()
            r = await task
            assert held.result(60)[1]
            return _root_of(r.headers["X-Trace-Id"]), t0, t1
        finally:
            release.set()
            await _close(client)

    root, t0, t1 = asyncio.run(go())
    by_name = {s.name: s for s, _ in _walk(root)}
    queue, search = by_name["engine.queue"], by_name["engine.search"]
    # the queue span covers the whole of the hold, and the work follows it
    assert queue.start <= t0 < t1 <= queue.end
    assert queue.end <= search.start


def test_other_endpoints_between_two_searches_leave_the_stage_counters_alone():
    """`GET /i/_doc/1`, `_msearch` and `_count` go through `call()` and
    `_run_search` too: none of them may reach the counters that the readers
    divide by the searches (`rest.server_ms` = `rest.search` - `engine.queue`
    - `engine.search`)."""
    async def go():
        client = await _client_with_index()
        try:
            await _search(client, "alpha gamma")
            before = _stage_counters()
            await _search(client, "beta delta")
            mid = _stage_counters()
            assert (await client.get("/i/_doc/1")).status == 200
            r = await client.post(
                "/i/_msearch", data=json.dumps({}) + "\n" + json.dumps(
                    {"query": {"match": {"body": "gamma zeta"}}}) + "\n",
                headers={"Content-Type": "application/x-ndjson"})
            assert r.status == 200, await r.text()
            assert (await client.get("/i/_count")).status == 200
            doc_root = TRACER.finished[-3]
            others = _stage_counters()
            await _search(client, "epsilon eta")
            return before, mid, others, _stage_counters(), doc_root
        finally:
            await _close(client)

    before, mid, others, after, doc_root = asyncio.run(go())
    assert doc_root.name == "http GET /{index}/_doc/{id}"
    assert [c.name for c in doc_root.children] == []
    assert others == mid
    for name in STAGES:
        n = 2 if name == "engine.collect" else 1
        for a, b in ((before, mid), (others, after)):
            assert (b[f"es.span.{name}.count"]
                    - a[f"es.span.{name}.count"]) == n, name


def test_the_root_span_is_named_by_its_route_and_mints_no_counter():
    async def go():
        client = await _client_with_index()
        try:
            r1 = await client.get("/i/_doc/1")
            keys = set(telemetry.metrics.snapshot()["counters"])
            r2 = await client.get("/i/_doc/2")
            r3 = await client.get("/no/such/route/at/all")
            assert r3.status == 404
            return ([_root_of(r.headers["X-Trace-Id"]) for r in (r1, r2)]
                    + [TRACER.finished[-1]],
                    keys, set(telemetry.metrics.snapshot()["counters"]))
        finally:
            await _close(client)

    (a, b, c), keys_before, keys_after = asyncio.run(go())
    assert a.name == b.name == "http GET /{index}/_doc/{id}"
    assert (a.attributes["path"], b.attributes["path"]) == ("/i/_doc/1",
                                                            "/i/_doc/2")
    assert c.name == "http GET <unmatched>"
    assert c.attributes["path"] == "/no/such/route/at/all"
    assert keys_after == keys_before
    assert not [k for k in keys_after if k.startswith("es.span.http")]


def test_a_span_outside_the_stages_is_recorded_and_summed_nowhere():
    before = _stage_counters()
    with TRACER.span("PUT /idx/_doc/17", index="idx") as s:
        pass
    assert TRACER.finished[-1] is s and s.end >= s.start
    assert _stage_counters() == before


def test_record_files_a_wait_under_the_current_span():
    n0 = _stage_counters().get("es.span.engine.queue.count", 0)
    with TRACER.span("rest.search") as outer:
        t0 = time.perf_counter_ns()
        t1 = time.perf_counter_ns()
        got = TRACER.record("engine.queue", t0, t1, why="test")
    assert outer.children == [got]
    assert (got.parent_span_id, got.trace_id) == (outer.span_id,
                                                  outer.trace_id)
    assert got.start == t0 * 1e-9 and got.end == t1 * 1e-9
    assert got.attributes == {"why": "test"}
    assert _stage_counters()["es.span.engine.queue.count"] == n0 + 1


def test_a_stage_outside_a_search_is_recorded_and_summed_nowhere():
    """`_msearch`, the serving wave and a library call enter the engine's
    stages with no `rest.search` above them: the counters are a solo
    search's, and stay so."""
    before = _stage_counters()
    with TRACER.span("http POST /_msearch") as root:
        with TRACER.span("engine.search"):
            with TRACER.span("engine.parse"):
                pass
        TRACER.record("engine.queue", time.perf_counter_ns(),
                      time.perf_counter_ns())
    assert [c.name for c in root.children] == ["engine.search", "engine.queue"]
    assert _stage_counters() == before


def test_a_search_adds_its_stages_to_the_counters_together_when_it_ends():
    before = _stage_counters()
    with TRACER.span("rest.search"):
        with TRACER.span("engine.search"):
            for _ in range(2):
                with TRACER.span("engine.collect"):
                    pass
        assert _stage_counters() == before      # nothing until it ends
    after = _stage_counters()
    moved = {k: after[k] - before.get(k, 0) for k in after
             if k.endswith(".count") and after[k] != before.get(k, 0)}
    assert moved == {"es.span.rest.search.count": 1,
                     "es.span.engine.search.count": 1,
                     "es.span.engine.collect.count": 2}


def test_a_child_span_starts_on_the_wall_clock_where_its_root_says():
    with TRACER.span("root") as root:
        with TRACER.span("child") as child:
            pass
    by_name = {d["name"]: d
               for d in TRACER.spans_for_trace(root.trace_id)}
    assert (by_name["child"]["start_unix"] - by_name["root"]["start_unix"]
            == pytest.approx(child.start - root.start, abs=1e-6))
    assert by_name["child"]["parent_span_id"] == root.span_id
    assert len(child.span_id) == 16 and child.span_id != root.span_id


def test_span_ids_are_sixteen_hex_digits_and_never_repeat():
    ids = [TRACER.span("s").span_id for _ in range(2000)]
    assert len(set(ids)) == len(ids)
    assert all(len(i) == 16 and int(i, 16) >= 0 for i in ids)


def test_a_span_that_raises_is_still_finished_and_counted():
    n0 = _stage_counters().get("es.span.engine.parse.count", 0)
    with pytest.raises(ValueError):
        with TRACER.span("rest.search") as outer:
            with TRACER.span("engine.parse"):
                raise ValueError("bad query")
    assert [c.name for c in outer.children] == ["engine.parse"]
    assert outer.children[0].end is not None and outer.end is not None
    assert _stage_counters()["es.span.engine.parse.count"] == n0 + 1
    assert TRACER.current_span() is None


def test_the_leaf_stages_are_annotated_only_while_a_capture_runs():
    """`ProfilerService` switches the annotations on at a capture's start and
    off at its stop (tests/test_flight_recorder.py drives a real one in a
    process of its own); outside it a span opens none."""
    opened = []

    class Annotation:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    def search():
        with TRACER.span("rest.search"):
            with TRACER.span("engine.search"):
                with TRACER.span("engine.plan"):
                    pass
            with TRACER.span("rest.respond"):
                pass

    assert telemetry._annotation is None
    search()
    assert opened == []
    telemetry._annotation = Annotation
    try:
        search()
    finally:
        telemetry.annotate_stages(False)
    assert opened == ["engine.plan", "rest.respond"]   # leaves, no parent
    search()
    assert len(opened) == 2


@pytest.fixture(scope="module")
def searcher():
    from elasticsearch_tpu.engine import Engine

    engine = Engine(None)
    idx = engine.create_index("s", {"properties": {"body": {"type": "text"}}})
    for i in range(300):
        idx.index_doc(str(i), {
            "body": f"{WORDS[i % 7]} {WORDS[(i + 3) % 7]} common"})
    idx.refresh()
    yield idx.searcher
    engine.close()


def _solo_counters() -> tuple[int, int]:
    c = telemetry.metrics.snapshot()["counters"]
    return (int(c.get("es.jit.cache.search_solo.hits", 0)),
            int(c.get("es.jit.cache.search_solo.misses", 0)))


def test_the_program_cache_counts_a_hit_for_an_equal_shape_and_a_miss_for_a_new_one(
        searcher):
    def search(text):
        return searcher.search({"match": {"body": text}}, size=3)

    search("alpha gamma")
    h0, m0 = _solo_counters()
    search("beta delta")                  # two terms again: the same program
    assert _solo_counters() == (h0 + 1, m0)
    search("alpha gamma epsilon zeta eta")
    assert _solo_counters() == (h0 + 1, m0 + 1)
    programs = [fn for fn in searcher._cache.values()
                if getattr(fn, "__name__", "") == "search_solo"]
    assert len(programs) >= 2


def test_the_compiled_search_is_named_search_solo_and_carries_its_scopes(
        searcher):
    st = searcher._agg_dispatch(query={"match": {"body": "alpha gamma"}},
                                size=3)
    fn, buffers = searcher._packed_program(
        st["node"], st["keys"], st["k"], None, (), st["params"],
        st["agg_params"])
    assert fn.__name__ == "search_solo"
    text = fn.lower(searcher.dev, buffers).as_text(debug_info=True)
    assert "jit(search_solo)" in text
    # a transformation wraps the scope it passes through: `vmap(score)`
    for scope in ("score", "topk"):
        assert re.search(rf"jit\(search_solo\)/(\w+\()*{scope}\)*/", text), scope


def _scoped_primitives(jaxpr, scope=""):
    """-> [(primitive, the scopes around it)] of a jaxpr's leaf equations; a
    nested jaxpr's own name stacks start anew under its equation's."""
    out = []
    for eqn in jaxpr.eqns:
        here = f"{scope}/{eqn.source_info.name_stack}"
        inner = [getattr(v, "jaxpr", v) for v in eqn.params.values()
                 if hasattr(v, "eqns") or hasattr(v, "jaxpr")]
        if not inner:
            out.append((eqn.primitive.name, here))
        for sub in inner:
            out.extend(_scoped_primitives(sub, here))
    return out


def test_the_selection_runs_whole_under_the_scope_topk():
    """`device.topk_ms` is the time of the operations that carry the scope
    `topk`: every operation the selection adds has to carry it, and nothing
    but plumbing may run outside `score` and `topk`."""
    import jax

    from elasticsearch_tpu.engine import Engine

    engine = Engine(None)
    try:
        idx = engine.create_index(
            "wide", {"properties": {"body": {"type": "text"}}})
        for i in range(1100):
            idx.index_doc(str(i), {"body": f"{WORDS[i % 7]} common"})
        idx.refresh()
        searcher = idx.searcher
        st = searcher._agg_dispatch(query={"match": {"body": "alpha common"}},
                                    size=1)
        fn, buffers = searcher._packed_program(
            st["node"], st["keys"], st["k"], None, (), st["params"],
            st["agg_params"])
        prims = _scoped_primitives(
            jax.make_jaxpr(fn)(searcher.dev, buffers).jaxpr)
    finally:
        engine.close()
    in_topk = [p for p, scope in prims if "topk" in scope]
    # the blocks' maxima, the two selections and the shards' merge, the
    # ascending sort, the gather of the chosen blocks
    assert in_topk.count("top_k") == 3 and in_topk.count("sort") == 1
    assert {"pad", "reduce_max", "gather", "reduce_sum"} <= set(in_topk)
    selection = {"top_k", "sort", "pad", "reduce_max", "reduce_min", "gather",
                 "select_n", "div", "rem"}
    stray = [(p, scope) for p, scope in prims
             if p in selection and "topk" not in scope
             and "score" not in scope]
    assert not stray, stray
    unscoped = {p for p, scope in prims
                if "topk" not in scope and "score" not in scope}
    # the parameters' unpacking, the total's sum over the shards and the
    # outputs' packing
    assert unscoped <= {"slice", "reshape", "squeeze", "bitcast_convert_type",
                        "convert_element_type", "reduce_sum",
                        "concatenate"}, unscoped


def test_persistent_cache_hits_are_counted_beside_compiles():
    import jax.monitoring

    from elasticsearch_tpu.monitoring.device import (install_compile_listener,
                                                     jit_stats)

    install_compile_listener()
    before = jit_stats()
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    after = jit_stats()
    assert after["persistent_cache_hits"] == before["persistent_cache_hits"] + 1
    assert after["compiles"] == before["compiles"]


def test_trace_dump_renders_the_stage_tree():
    with TRACER.span("http POST /{index}/_search") as root:
        with TRACER.span("rest.search"):
            with TRACER.span("engine.search"):
                pass
    spec = importlib.util.spec_from_file_location(
        "trace_dump", os.path.join(REPO, "scripts", "trace_dump.py"))
    td = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(td)
    buf = io.StringIO()
    td.render(telemetry.stitch_trace(TRACER.spans_for_trace(root.trace_id)),
              out=buf)
    lines = buf.getvalue().splitlines()
    rows = [ln for ln in lines if "search" in ln]
    assert [next(n for n in ("http POST", "rest.search", "engine.search")
                 if n in ln) for ln in rows[:3]] == [
        "http POST", "rest.search", "engine.search"]
