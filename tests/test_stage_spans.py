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
# the stages of a search's tree, each with the parents it may have
PARENT = {
    "rest.search": ("http POST /{index}/_search",),
    "engine.queue": ("rest.search",), "engine.search": ("rest.search",),
    "rest.respond": ("rest.search",), "rest.read": ("rest.search",),
    "rest.resume": ("rest.search",),
    "executeQueryPhase": ("engine.search",), "engine.route": ("engine.search",),
    "engine.parse": ("executeQueryPhase",), "engine.plan": ("executeQueryPhase",),
    "engine.dispatch": ("executeQueryPhase",),
    "engine.fetch": ("executeQueryPhase",),
    "engine.unpack": ("executeQueryPhase",),
    "engine.collect": ("executeQueryPhase",),
    # the dispatch's and the fetch's bookkeeping, then the search's slow log
    "engine.account": ("executeQueryPhase", "engine.search"),
}
# the stages a search without aggregations enters more than once:
# `engine.collect` twice (the result, then its hits), `engine.account` three
# times (PARENT)
ENTERED = {"engine.collect": 2, "engine.account": 3}


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
                     **{n: ENTERED.get(n, 1) for n in STAGES}}
    assert {s.trace_id for s, _ in spans} == {root.trace_id}
    assert len({s.span_id for s, _ in spans}) == len(spans)
    for s, parent in spans:
        if parent is None:
            continue
        assert parent.name in PARENT[s.name], (s.name, parent.name)
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
        n = ENTERED.get(name, 1)
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
        n = ENTERED.get(name, 1)
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


# ---- the host time between and beneath the stages ---------------------------

def _reader(name: str):
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        import run as harness
    finally:
        sys.path.pop(0)
    return harness, harness.layer_reader(os.path.join(REPO, "benchmark"),
                                          name)


def test_the_new_stages_move_with_every_search_and_with_nothing_else():
    async def go():
        client = await _client_with_index()
        try:
            await _search(client, "alpha gamma")
            before = _stage_counters()
            for text in ("beta delta", "gamma zeta", "alpha eta"):
                await _search(client, text)
            mid = _stage_counters()
            assert (await client.get("/i/_count")).status == 200
            return before, mid, _stage_counters()
        finally:
            await _close(client)

    before, mid, after = asyncio.run(go())
    searches = (mid["es.span.rest.search.count"]
                - before["es.span.rest.search.count"])
    assert searches == 3
    for name in ("engine.route", "engine.unpack", "engine.account",
                 "rest.read", "rest.resume"):
        moved = (mid[f"es.span.{name}.count"]
                 - before[f"es.span.{name}.count"])
        assert moved == ENTERED.get(name, 1) * searches, name
        assert mid[f"es.span.{name}.ns"] > before[f"es.span.{name}.ns"]
    assert after == mid


def test_engine_search_less_its_stages_is_what_engine_unnamed_ms_reads():
    """One search: its `engine.search` less every stage under it, from its
    own tree, is what the reader gives on the counters around it."""
    async def go():
        client = await _client_with_index()
        try:
            await _search(client, "alpha gamma")
            before = telemetry.metrics.snapshot()
            r = await _search(client, "beta delta")
            return (before, telemetry.metrics.snapshot(),
                    _root_of(r.headers["X-Trace-Id"]))
        finally:
            await _close(client)

    before, after, root = asyncio.run(go())
    search = next(s for s, _ in _walk(root) if s.name == "engine.search")
    children = [s for s, _ in _walk(search)
                if s is not search and s.name in STAGES]
    assert {s.name for s in children} == {
        "engine.route", "engine.parse", "engine.plan", "engine.account",
        "engine.dispatch", "engine.fetch", "engine.unpack", "engine.collect"}
    unnamed_ns = (search._t1 - search._t0) - sum(s._t1 - s._t0
                                                 for s in children)
    assert unnamed_ns > 0
    harness, read = _reader("engine.unnamed_ms")
    run = harness.Run()
    run.before, run.after = before, after
    assert read(run) == pytest.approx(unnamed_ns * 1e-6, rel=1e-12)


def test_the_client_gap_counts_the_second_request_on_a_connection():
    """A request that follows an answer on its kept-alive connection adds
    the time between them; the first request of a connection adds none."""
    import aiohttp

    def gap():
        c = telemetry.metrics.snapshot()["counters"]
        return (c["es.rest.client_gap.ns"], c["es.rest.client_gap.count"])

    async def go():
        client = await _client_with_index()
        url = client.make_url("/i/_search")
        body = {"query": {"match": {"body": "alpha"}}}
        try:
            async with aiohttp.ClientSession() as fresh:
                g0 = gap()
                async with fresh.post(url, json=body) as r:
                    assert r.status == 200
                    await r.read()
                g1 = gap()
                async with fresh.post(url, json=body) as r:
                    assert r.status == 200
                    await r.read()
                g2 = gap()
            return g0, g1, g2
        finally:
            await _close(client)

    g0, g1, g2 = asyncio.run(go())
    assert g1 == g0                      # a new connection: no answer before
    assert g2[1] == g1[1] + 1 and g2[0] > g1[0]


class _Annotations:
    """A stand-in for `jax.profiler.TraceAnnotation` that holds, for each
    thread, how many are open at once."""

    def __init__(self):
        self.opened, self.deepest = [], {}
        self._depth = collections.Counter()
        self._lock = threading.Lock()

    def __call__(self, name):
        outer = self

        class Annotation:
            def __enter__(self):
                me = threading.get_ident()
                with outer._lock:
                    outer.opened.append(name)
                    outer._depth[me] += 1
                    outer.deepest[me] = max(outer.deepest.get(me, 0),
                                            outer._depth[me])
                return self

            def __exit__(self, *exc):
                with outer._lock:
                    outer._depth[threading.get_ident()] -= 1
                return False

        return Annotation()


def test_no_two_annotations_are_open_at_once_on_the_solo_path():
    stub = _Annotations()

    async def go():
        client = await _client_with_index()
        try:
            await _search(client, "alpha gamma")
            telemetry._annotation = stub
            try:
                await _search(client, "beta delta")
            finally:
                telemetry.annotate_stages(False)
        finally:
            await _close(client)

    asyncio.run(go())
    assert set(stub.opened) == ANNOTATED_STAGES - set(WAVE_STAGES)
    assert collections.Counter(stub.opened)["engine.account"] == 3
    assert max(stub.deepest.values()) == 1


def test_no_two_annotations_are_open_at_once_on_the_wave_threads():
    """A wave carries the solo path's stages inside its own (a generic
    lane's parsing, planning and launch inside `engine.wave_plan`, its hits'
    `engine.collect` inside `engine.wave_finish`): they open none."""
    stub = _Annotations()

    async def go():
        client = await _client_with_index()
        try:
            await client.put("/_cluster/settings", json={"persistent": {
                "serving.enabled": True}})
            body = {"query": {"match": {"body": "alpha"}},
                    "aggs": {"n": {"value_count": {"field": "_id"}}}}
            r = await client.post("/i/_search", json=body)  # compiles
            assert r.status == 200, await r.text()
            telemetry._annotation = stub
            try:
                rs = await asyncio.gather(*(
                    client.post("/i/_search", json=b) for b in (
                        body, {"query": {"match": {"body": "beta"}}})))
                assert [r.status for r in rs] == [200, 200]
            finally:
                telemetry.annotate_stages(False)
        finally:
            await _close(client)

    asyncio.run(go())
    assert {"engine.wave_plan", "engine.wave_fetch",
            "engine.wave_finish"} <= set(stub.opened)
    assert max(stub.deepest.values()) == 1


def test_time_kernels_tail_is_engine_account_only_in_a_searchs_own_work():
    from elasticsearch_tpu.telemetry import WaveStages, time_kernel

    with TRACER.span("rest.search") as search:
        with time_kernel("test.kernel", queries=1):
            pass
    assert [c.name for c in search.children] == ["engine.account"]
    n = len(TRACER.finished)
    with time_kernel("test.kernel", queries=1):    # a thread with no span
        pass
    assert len(TRACER.finished) == n
    stages = WaveStages()
    with stages.stage("engine.wave_fetch"):
        with time_kernel("test.kernel", queries=1):
            pass
    fetch = TRACER.finished[-1]
    assert fetch.name == "engine.wave_fetch" and fetch.children == []


def test_the_xla_check_counts_what_it_spends():
    import jax
    import jax.numpy as jnp

    from elasticsearch_tpu.monitoring import xla_introspect

    def ns():
        return telemetry.metrics.snapshot()["counters"].get(
            "es.costmodel.xla_check.ns", 0)

    before = ns()
    fn = jax.jit(lambda x: x * 2)
    x = jnp.arange(8.0)
    xla_introspect.check_dispatch("test.xla_check_ns", fn, (x,))
    after = ns()
    assert after > before
    xla_introspect.check_dispatch("test.xla_check_ns", fn, (x,))  # seen
    assert ns() == after


@pytest.fixture
def waves_of(tmp_path, monkeypatch):
    """A live serving front end over one index, and every wave's stages
    with its claim, as `_count_wave` counts them."""
    from elasticsearch_tpu.engine import Engine
    from elasticsearch_tpu.serving.service import ServingService

    engine = Engine(str(tmp_path / "data"))
    idx = engine.create_index("w", {"properties": {
        "body": {"type": "text"}, "tag": {"type": "keyword"}}})
    for i in range(300):
        idx.index_doc(str(i), {
            "body": f"{WORDS[i % 7]} {WORDS[(i + 3) % 7]} common",
            "tag": WORDS[i % 3]})
    idx.refresh()
    counted = []
    original = ServingService._count_wave

    def count_wave(self, state, stages, rows):
        original(self, state, stages, rows)
        counted.append((state["members"][0].claim_ns, stages, state))

    monkeypatch.setattr(ServingService, "_count_wave", count_wave)
    svc = engine.serving
    try:
        yield svc, counted
    finally:
        svc.stop()
        engine.close()


def test_every_waves_seven_stages_sum_to_its_claim_to_done(waves_of):
    from concurrent.futures import wait

    svc, counted = waves_of
    bodies = [{"query": {"match": {"body": w}}, "size": 5}
              for w in ("alpha", "beta gamma", "delta", "common")]
    bodies.append({"query": {"term": {"tag": "beta"}}, "size": 3,
                   "aggs": {"t": {"terms": {"field": "tag"}}}})
    for _ in range(3):
        futs = [svc.submit(svc.classify("w", b, {})) for b in bodies]
        wait(futs, timeout=120)
        assert all(f.exception() is None for f in futs)
    svc.drain()
    assert counted
    seven = WAVE_STAGES + telemetry.WAVE_HANDOFFS
    for claim, stages, state in counted:
        done = stages.bounds["engine.wave_finish"][1]
        # a wave whose lanes launched nothing of their own has no launch
        assert set(seven) - {"engine.wave_launch"} <= set(stages.sums) \
            <= set(seven)
        assert sum(stages.ns(s) for s in seven) == done - claim
        assert all(stages.ns(s) >= 0 for s in seven)
        assert [stages.sums[s][1] for s in telemetry.WAVE_HANDOFFS] \
            == [1, 1, 1]
    # the flight recorder reads the same stamps: its hand-offs are the
    # stages', and its segments end where the stages do
    recs = svc.flight_recorder()["waves"][-len(counted):]
    for rec, (claim, stages, _state) in zip(recs, counted):
        assert rec["handoffs_ms"] == {
            short: round(stages.ns(f"engine.wave_{short}") / 1e6, 4)
            for short in ("queue", "handoff", "rejoin")}
        b = stages.bounds
        assert rec["segments_ms"]["plan"] == round(
            (b["engine.wave_plan"][1] - claim) / 1e6, 4)


def test_a_served_search_reads_and_resumes_under_its_rest_search():
    async def go():
        client = await _client_with_index()
        try:
            await client.put("/_cluster/settings", json={"persistent": {
                "serving.enabled": True}})
            await _search(client, "alpha gamma")
            before = _stage_counters()
            r = await _search(client, "beta delta")
            return (before, _stage_counters(),
                    _root_of(r.headers["X-Trace-Id"]))
        finally:
            await _close(client)

    before, after, root = asyncio.run(go())
    by_name = collections.defaultdict(list)
    for s, parent in _walk(root):
        by_name[s.name].append(parent.name if parent is not None else None)
    assert by_name["rest.read"] == ["rest.search"]
    assert by_name["rest.resume"] == ["rest.search"]
    for name in ("engine.route", "engine.unpack", "engine.account"):
        assert name not in by_name
        assert (after[f"es.span.{name}.count"]
                == before[f"es.span.{name}.count"]), name
    for name in ("rest.read", "rest.resume", "rest.search"):
        assert (after[f"es.span.{name}.count"]
                - before[f"es.span.{name}.count"]) == 1, name
