"""The engine thread and its completer (`common/engine_thread.py`): a solo
`_search` whose program is launched hands its fetch and everything after it
to the completer while another search waits for the engine thread, and only
then. Answers stay the serial path's, byte for byte; every task that is not a
solo search starts with the completer empty, so no answer mixes two packs;
at most two solo programs are in flight. The executor alone first, then
through the REST layer under eight concurrent clients, on the CPU. Waits are
bounded and nothing asserts on a duration."""

import asyncio
import collections
import json
import sys
import threading
import time

import pytest

from elasticsearch_tpu import telemetry
from elasticsearch_tpu.common.engine_thread import EngineExecutor
from elasticsearch_tpu.parallel.sharded import StackedSearcher

WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta"]
QUERIES = [f"{a} {b}" for a in WORDS for b in WORDS[2:]]
CLIENTS = 8
HANDED_OFF = "es.search.solo.handed_off"


# -- the executor alone ---------------------------------------------------------

class _Count:
    def __init__(self, n=0):
        self.n = n

    def __call__(self):
        return self.n


def _handing(ex, started, release, out, tag):
    """A search task that hands off once launched, then waits for `release`
    on the completer."""
    def task():
        out.append((tag, "engine", ex.hand_off()))
        started.set()
        assert release.wait(30)
        out.append((tag, "done", threading.current_thread().name))
        return tag
    return task


def test_a_search_hands_off_only_while_another_waits():
    waiting = _Count(0)
    ex = EngineExecutor(waiting)
    try:
        assert ex.submit_search(ex.hand_off).result(30) is False
        waiting.n = 1
        started, release, out = threading.Event(), threading.Event(), []
        first = ex.submit_search(_handing(ex, started, release, out, "a"))
        assert started.wait(30)
        # the engine is free while "a" is still with the completer
        waiting.n = 0
        assert ex.submit_search(lambda: "next").result(30) == "next"
        assert not first.done()
        release.set()
        assert first.result(30) == "a"
        assert out[0] == ("a", "engine", True)
    finally:
        release.set()
        ex.shutdown(wait=True)
    assert not any(t.is_alive() for t in ex._threads)


def test_a_task_that_is_no_search_waits_for_the_completer():
    ex = EngineExecutor(_Count(1))
    started, release, out = threading.Event(), threading.Event(), []
    try:
        first = ex.submit_search(_handing(ex, started, release, out, "a"))
        assert started.wait(30)
        other = ex.submit(lambda: out.append(("refresh",)))
        search = ex.submit_search(lambda: out.append(("search",)))
        assert not other.done()
        release.set()
        first.result(30), other.result(30), search.result(30)
    finally:
        release.set()
        ex.shutdown(wait=True)
    # in order: the refresh after "a" was done, the search after the refresh
    assert [o[0] for o in out] == ["a", "a", "refresh", "search"]


def test_at_most_one_search_is_with_the_completer():
    ex = EngineExecutor(_Count(1))
    release_a, release_b = threading.Event(), threading.Event()
    started_a, started_b, out = threading.Event(), threading.Event(), []
    try:
        a = ex.submit_search(_handing(ex, started_a, release_a, out, "a"))
        assert started_a.wait(30)
        b = ex.submit_search(_handing(ex, started_b, release_b, out, "b"))
        # "b" waits in its hand-off for "a" to leave the completer
        for _ in range(3000):
            if ex._parked:
                break
            time.sleep(0.01)
        assert ex._parked and not started_b.is_set()
        release_a.set()
        assert started_b.wait(30)
        release_b.set()
        assert (a.result(30), b.result(30)) == ("a", "b")
    finally:
        release_a.set()
        release_b.set()
        ex.shutdown(wait=True)
    assert [o[:2] for o in out] == [("a", "engine"), ("a", "done"),
                                    ("b", "engine"), ("b", "done")]


def test_the_completer_takes_the_engine_back_while_the_engine_waits_for_it():
    """A retry on the completer (`engine_turn`) runs while the engine's
    thread is parked in the next search's hand-off, never beside engine
    work."""
    ex = EngineExecutor(_Count(1))
    inside = []    # who runs engine work now
    seen = []

    def engine_work(tag):
        inside.append(tag)
        seen.append(len(inside))
        time.sleep(0.01)
        inside.remove(tag)

    go_a, started_a = threading.Event(), threading.Event()

    def a():
        assert ex.hand_off()
        started_a.set()
        assert go_a.wait(30)
        with ex.engine_turn():
            engine_work("a-retry")
        return "a"

    def b():
        engine_work("b-plan")
        go_a.set()
        ex.hand_off()       # parks until "a" is done
        return "b"

    try:
        fa = ex.submit_search(a)
        assert started_a.wait(30)
        fb = ex.submit_search(b)
        assert (fa.result(30), fb.result(30)) == ("a", "b")
    finally:
        go_a.set()
        ex.shutdown(wait=True)
    assert seen == [1, 1]


def test_a_cancelled_task_never_runs_and_shutdown_refuses_more():
    ex = EngineExecutor(_Count(0))
    gate, ran = threading.Event(), []
    held = ex.submit(gate.wait, 30)
    queued = ex.submit(ran.append, 1)
    assert queued.cancel()
    gate.set()
    assert held.result(30) is True
    ex.shutdown(wait=True)
    assert ran == []
    with pytest.raises(RuntimeError):
        ex.submit(ran.append, 2)


# -- through the REST layer -----------------------------------------------------

def _counters() -> dict:
    return dict(telemetry.metrics.snapshot()["counters"])


def _added(before, after, key):
    return after.get(key, 0) - before.get(key, 0)


def _doc(i, extra=""):
    return {"body": f"{WORDS[i % 7]} {WORDS[(i + 3) % 7]} "
                    f"{WORDS[(i * 5) % 7]} common{extra}"}


async def _bulk(client, index, docs, deletes=()):
    lines = [json.dumps({"delete": {"_id": doc_id}}) for doc_id in deletes]
    for doc_id, doc in docs:
        lines.append(json.dumps({"index": {"_id": doc_id}}))
        lines.append(json.dumps(doc))
    r = await client.post(f"/{index}/_bulk", data="\n".join(lines) + "\n",
                          headers={"Content-Type": "application/x-ndjson"})
    assert r.status == 200, await r.text()
    assert not (await r.json())["errors"]


async def _client(settings=None, docs=600):
    from aiohttp.test_utils import TestClient, TestServer

    from elasticsearch_tpu.rest.app import make_app

    client = TestClient(TestServer(make_app()))
    await client.start_server()
    await client.put("/_cluster/settings", json={"persistent": {
        "indices.requests.cache.enable": False}})
    r = await client.put("/i", json={
        "settings": settings or {},
        "mappings": {"properties": {"body": {"type": "text"}}}})
    assert r.status == 200, await r.text()
    # past what an incremental refresh keeps in a tail segment
    await _bulk(client, "i", [(str(i), _doc(i)) for i in range(docs)])
    assert (await client.post("/i/_refresh")).status == 200
    return client


async def _search(client, text):
    """-> (status, the answer less `took`, the trace id)."""
    r = await client.post("/i/_search", json={
        "query": {"match": {"body": text}}, "size": 10})
    body = await r.json()
    body.pop("took", None)
    return r.status, json.dumps(body, sort_keys=True), \
        r.headers.get("X-Trace-Id")


async def _serial(client):
    out = {}
    for q in QUERIES:
        status, body, _ = await _search(client, q)
        assert status == 200, body
        out[q] = body
    return out


async def _clients(client, rounds, until=None):
    """CLIENTS closed-loop clients, each `rounds` searches from its own
    place in QUERIES (and on until `until` is set). -> [(query, status,
    answer, trace id)]."""
    async def one(k):
        got = []
        j = 0
        while j < rounds or (until is not None and not until.is_set()):
            q = QUERIES[(k * 5 + j) % len(QUERIES)]
            got.append((q, *await _search(client, q)))
            j += 1
        return got

    return [x for part in await asyncio.gather(
        *(one(k) for k in range(CLIENTS))) for x in part]


def _run(go):
    return asyncio.run(go())


def test_eight_clients_get_the_serial_answers_byte_for_byte():
    """Under a switch interval a hundred times shorter than the default, so
    that the threads interleave as finely as they can."""
    async def go():
        client = await _client()
        try:
            serial = await _serial(client)
            before = _counters()
            switch = sys.getswitchinterval()
            sys.setswitchinterval(switch / 100)
            try:
                got = await _clients(client, 30)
            finally:
                sys.setswitchinterval(switch)
            return serial, got, before, _counters()
        finally:
            await client.close()

    serial, got, before, after = _run(go)
    assert all(status == 200 for _q, status, _b, _t in got)
    assert [b for q, _s, b, _t in got] == [serial[q] for q, *_ in got]
    # the answers came through the completer, most of them
    assert _added(before, after, HANDED_OFF) > len(got) // 4


def test_handed_off_stays_0_under_one_client_and_moves_under_eight():
    async def go():
        client = await _client()
        try:
            await _search(client, QUERIES[0])
            c0 = _counters()
            for q in QUERIES:
                assert (await _search(client, q))[0] == 200
            c1 = _counters()
            await _clients(client, 20)
            return c0, c1, _counters()
        finally:
            await client.close()

    c0, c1, c2 = _run(go)
    assert c0[HANDED_OFF] >= 0                      # shipped from the start
    assert _added(c0, c1, HANDED_OFF) == 0
    assert _added(c0, c1, f"es.span.{telemetry.SOLO_HANDOFF}.count") == 0
    assert _added(c1, c2, HANDED_OFF) > 0
    assert (_added(c1, c2, f"es.span.{telemetry.SOLO_HANDOFF}.count")
            == _added(c1, c2, HANDED_OFF))


@pytest.mark.parametrize("refresh", ["explicit", "by_a_search"])
def test_writes_refresh_and_delete_in_flight_answer_from_one_pack(
        refresh, monkeypatch):
    """`_bulk`, a refresh (the `_refresh` endpoint, or the one a search
    makes once `refresh_interval` has passed since the last) and an index's
    deletion amid eight clients: every answer is the old pack's or the new
    one's, and none is an error. The completer fetches only once the engine
    thread waits for it or has nothing to do, so that a refresh the next
    search makes would come before the hits' formatting if nothing held it
    back."""
    def after_the_next_plan(pool):
        end = time.monotonic() + 10
        while not (pool._parked or (pool._engine is None
                                    and pool._work_queue.empty())):
            assert time.monotonic() < end
            time.sleep(0.0005)

    pools = _fetch_on_the_completer(monkeypatch, after_the_next_plan)

    async def go():
        client = await _client({"refresh_interval": "500ms"})
        pools.append(client.server.app["pool"])
        index = client.server.app["engine"].indices["i"]
        r = await client.put("/other", json={"mappings": {"properties": {
            "body": {"type": "text"}}}})
        assert r.status == 200
        try:
            old = await _serial(client)
            done = asyncio.Event()

            async def writer():
                # as if the pack were new: the searches that follow the
                # writes hand over while the interval runs, until one of
                # them refreshes
                index._last_refresh = time.monotonic()
                # more documents than a tail segment keeps, matching every
                # query, so that the new pack's answers differ from the old;
                # the deletions move every later document to another place
                await _bulk(client, "i", [
                    (f"n{i}", {"body": f"{' '.join(WORDS)} common"})
                    for i in range(300)], deletes=[str(i) for i in range(40)])
                if refresh == "explicit":
                    r = await client.post("/i/_refresh")
                    assert r.status == 200
                    assert (await r.json())["_shards"]["failed"] == 0
                while index._dirty:
                    await asyncio.sleep(0.01)
                assert (await client.delete("/other")).status == 200
                await asyncio.sleep(0.05)
                done.set()

            before = _counters()
            got, _ = await asyncio.gather(_clients(client, 10, done), writer())
            after = _counters()
            new = await _serial(client)
            return old, new, got, before, after
        finally:
            await client.close()

    old, new, got, before, after = _run(go)
    assert all(status == 200 for _q, status, _b, _t in got)
    assert all(b in (old[q], new[q]) for q, _s, b, _t in got)
    assert any(b == new[q] != old[q] for q, _s, b, _t in got)
    assert _added(before, after, HANDED_OFF) > 0


def _fetch_on_the_completer(monkeypatch, when):
    """Patch the solo fetch: `when(pool)` runs first wherever the fetch runs
    on the completer."""
    real = StackedSearcher._fetch
    pools = []

    def fetch(self, outs):
        if pools and pools[0]._completer is threading.current_thread():
            when(pools[0])
        return real(self, outs)

    monkeypatch.setattr(StackedSearcher, "_fetch", fetch)
    return pools


def test_an_error_in_a_handed_off_fetch_reaches_its_own_request_alone(
        monkeypatch):
    fired = []

    def fail(_pool):
        if not fired:
            fired.append(threading.current_thread().name)
            raise RuntimeError("fetch failed on the completer")

    pools = _fetch_on_the_completer(monkeypatch, fail)

    async def go():
        client = await _client()
        pools.append(client.server.app["pool"])
        try:
            serial = await _serial(client)
            got = await _clients(client, 10)
            status, body, _ = await _search(client, QUERIES[3])
            return serial, got, (status, body)
        finally:
            await client.close()

    serial, got, last = _run(go)
    assert len(fired) == 1
    failed = [(q, b) for q, status, b, _t in got if status != 200]
    assert len(failed) == 1
    assert "fetch failed on the completer" in failed[0][1]
    assert all(b == serial[q] for q, status, b, _t in got if status == 200)
    assert last == (200, serial[QUERIES[3]])


def test_a_device_oom_in_a_handed_off_fetch_reruns_on_the_engine(monkeypatch):
    """The degradation's re-run is engine work: the completer takes the
    engine back for it (`engine_turn`), and the answer is the serial one."""
    from elasticsearch_tpu.common import faults

    fired = []

    def oom(pool):
        if not fired:
            fired.append(pool._engine)
            raise faults.InjectedDeviceOOM("device.fetch")

    pools = _fetch_on_the_completer(monkeypatch, oom)

    async def go():
        client = await _client()
        pools.append(client.server.app["pool"])
        try:
            serial = await _serial(client)
            before = _counters()
            got = await _clients(client, 10)
            return serial, got, before, _counters()
        finally:
            await client.close()

    serial, got, before, after = _run(go)
    assert len(fired) == 1
    assert all(status == 200 for _q, status, _b, _t in got)
    assert [b for q, _s, b, _t in got] == [serial[q] for q, *_ in got]
    assert _added(before, after, "es.resilience.device.oom") == 1


def _walk(span, parent=None):
    yield span, parent
    for child in span.children:
        yield from _walk(child, span)


def test_a_handed_off_search_keeps_its_stages_under_its_own_rest_search():
    async def go():
        client = await _client()
        try:
            await _search(client, QUERIES[0])
            before = _counters()
            got = await _clients(client, 20)
            return got, before, _counters()
        finally:
            await client.close()

    got, before, after = _run(go)
    roots = {r.trace_id: r for r in telemetry.TRACER.finished}
    handed = 0
    for _q, status, _b, trace_id in got:
        assert status == 200
        root = roots.get(trace_id)
        if root is None:     # the tracer keeps the last 256 roots
            continue
        spans = list(_walk(root))
        by_name = collections.defaultdict(list)
        for s, _p in spans:
            by_name[s.name].append(s)
        if not by_name[telemetry.SOLO_HANDOFF]:
            continue
        handed += 1
        (search,), (engine,) = by_name["rest.search"], by_name["engine.search"]
        inside = [s for s, _p in _walk(engine)]
        for name in ("engine.fetch", "engine.unpack", "engine.collect",
                     telemetry.SOLO_HANDOFF):
            for s in by_name[name]:
                assert s in inside, name
                assert engine.start <= s.start <= s.end <= engine.end
        assert search.start <= engine.start <= engine.end <= search.end
        stages = sum(s.duration_ms for name in (
            "engine.route", "engine.parse", "engine.plan", "engine.account",
            "engine.dispatch", "engine.fetch", "engine.unpack",
            "engine.collect") for s in by_name[name])
        assert engine.duration_ms - stages >= 0
    assert handed > 0
    # `engine.unnamed_ms` of the window, as its reader computes it
    whole = _added(before, after, "es.span.engine.search.ns")
    parts = sum(_added(before, after, f"es.span.engine.{s}.ns") for s in (
        "route", "parse", "plan", "account", "dispatch", "fetch", "unpack",
        "collect"))
    assert whole - parts >= 0


def test_at_most_two_solo_programs_are_in_flight(monkeypatch):
    """The completer held inside its fetch: the engine thread launches one
    more search and waits in its hand-off; nothing else is launched until
    the completer lets go."""
    hold, held = threading.Event(), threading.Event()
    launched, fetched = [], []
    real_launch, real_fetch = StackedSearcher._launch, StackedSearcher._fetch
    pools = []

    def launch(self, fn, buffers):
        launched.append(1)
        return real_launch(self, fn, buffers)

    def fetch(self, outs):
        if (pools and pools[0]._completer is threading.current_thread()
                and not held.is_set()):
            held.set()
            assert hold.wait(60)
        got = real_fetch(self, outs)
        fetched.append(1)
        return got

    monkeypatch.setattr(StackedSearcher, "_launch", launch)
    monkeypatch.setattr(StackedSearcher, "_fetch", fetch)

    async def go():
        client = await _client()
        pool = client.server.app["pool"]
        pools.append(pool)
        loop = asyncio.get_running_loop()
        try:
            await _serial(client)
            task = asyncio.ensure_future(_clients(client, 4))
            assert await loop.run_in_executor(None, held.wait, 60)
            # the engine thread reaches the next search's hand-off and
            # waits there
            for _ in range(6000):
                if pool._parked:
                    break
                await asyncio.sleep(0.005)
            assert pool._parked
            in_flight = set()
            for _ in range(20):
                in_flight.add(len(launched) - len(fetched))
                await asyncio.sleep(0.005)
            hold.set()
            got = await task
            return in_flight, got, len(launched) - len(fetched)
        finally:
            hold.set()
            await client.close()

    in_flight, got, left = _run(go)
    # the one held on the completer, and the one whose hand-off waits
    assert in_flight == {2}
    assert left == 0
    assert all(status == 200 for _q, status, _b, _t in got)
