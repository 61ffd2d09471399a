"""HTTP client, loader and load generators. Grown from chip_smoke.py's
`Client` and `load`. Standard library only; one process, one thread per
client."""

from __future__ import annotations

import concurrent.futures as cf
import http.client
import json
import threading
import time

from .stats import Request

JSON = {"Content-Type": "application/json"}
NDJSON = {"Content-Type": "application/x-ndjson"}


class Client:
    """One connection per call: the server drops a kept-alive connection
    after 75 idle seconds, and set-up's phases last longer."""

    def __init__(self, port: int, timeout: float = 900.0):
        self.port, self.timeout = port, timeout

    def call(self, method: str, path: str, body=None, ndjson: bool = False):
        if body is not None and not isinstance(body, (bytes, str)):
            body = json.dumps(body)
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=self.timeout)
        try:
            conn.request(method, path, body=body,
                         headers=NDJSON if ndjson else JSON)
            resp = conn.getresponse()
            raw = resp.read()
        finally:
            conn.close()
        if resp.status >= 300:
            raise RuntimeError(
                f"{method} {path} -> {resp.status}: {raw[:600]!r}")
        return json.loads(raw)

    def node_stats(self) -> dict:
        return next(iter(self.call("GET", "/_nodes/stats")["nodes"].values()))


def load(c: Client, index: str, create: dict, n: int, payload, bulk_docs: int,
         say) -> dict:
    """Create the index with the body `create`, `_bulk` documents 0..n-1 in
    requests of `bulk_docs` (`payload(lo, hi)` makes each body), `_refresh`.
    Raises on an unacknowledged item or a failed shard. -> seconds of each
    phase."""
    c.call("PUT", f"/{index}", create)
    t0 = time.perf_counter()
    acked = 0
    with cf.ThreadPoolExecutor(1) as pool:  # build the next body meanwhile
        def body(lo):
            return payload(lo, min(lo + bulk_docs, n))
        nxt = pool.submit(body, 0)
        for lo in range(0, n, bulk_docs):
            data = nxt.result()
            if lo + bulk_docs < n:
                nxt = pool.submit(body, lo + bulk_docs)
            res = c.call("POST", f"/{index}/_bulk", data, ndjson=True)
            if res["errors"]:
                raise RuntimeError(f"_bulk at {lo} reported errors: "
                                   f"{json.dumps(res['items'][:2])[:600]}")
            acked += len(res["items"])
    load_s = time.perf_counter() - t0
    if acked != n:
        raise RuntimeError(f"_bulk acknowledged {acked} of {n} documents")
    t0 = time.perf_counter()
    shards = c.call("POST", f"/{index}/_refresh")["_shards"]
    refresh_s = time.perf_counter() - t0
    if shards["failed"]:  # a refresh that threw still answers 200
        raise RuntimeError(f"_refresh failed: {json.dumps(shards)[:600]}")
    say(f"load: {n} docs in {load_s:.1f} s ({n / load_s:.0f} docs/s); "
        f"refresh {refresh_s:.1f} s ({n / refresh_s:.0f} docs/s)")
    return {"load_s": load_s, "refresh_s": refresh_s, "docs": n}


class LoadGenerator:
    """Sends `POST /<index>/_search` for queries of the pool over `clients`
    kept-alive connections, one thread each. `digest(answer)` gives what a
    `Request` keeps of an answer from `took_ms` on (the cell's kind's).

    Closed loop (`rate` None): a client sends its next request when the last
    is answered, starting at its own offset (`pool / clients` apart).
    Open loop (`rate` requests a second over all clients): requests are due
    on a fixed schedule and are timed from when they were due.
    """

    def __init__(self, port: int, index: str, bodies: list[bytes], digest,
                 clients: int, rate: float | None = None,
                 request_timeout: float = 120.0):
        self.port, self.path = port, f"/{index}/_search"
        self.bodies, self.digest = bodies, digest
        self.clients, self.rate = clients, rate
        self.request_timeout = request_timeout

    def _send(self, conn, q: int, sent: float, t_zero: float) -> Request:
        try:
            conn.request("POST", self.path, body=self.bodies[q], headers=JSON)
            resp = conn.getresponse()
            raw = resp.read()
            done = time.perf_counter() - t_zero
        except (OSError, http.client.HTTPException):
            conn.close()
            return Request(q, sent, time.perf_counter() - t_zero, 0)
        if resp.status != 200:
            return Request(q, sent, done, resp.status)
        try:
            return Request(q, sent, done, 200, *self.digest(json.loads(raw)))
        except (ValueError, KeyError, TypeError):
            return Request(q, sent, done, -1)  # 200 with an unreadable body

    def _client(self, who: int, t_zero: float, stop_at: float | None,
                count: int | None, out: list) -> None:
        """One client: until `stop_at` (seconds from t_zero) or for `count`
        requests."""
        n = len(self.bodies)
        q = (who * n) // self.clients
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=self.request_timeout)
        mine = []
        try:
            i = 0
            while count is None or i < count:
                now = time.perf_counter() - t_zero
                if self.rate is not None:
                    due = (i * self.clients + who) / self.rate
                    if stop_at is not None and due >= stop_at:
                        break
                    if due > now:
                        time.sleep(due - now)
                    sent = due
                else:
                    if stop_at is not None and now >= stop_at:
                        break
                    sent = now
                mine.append(self._send(conn, q, sent, t_zero))
                q = (q + 1) % n
                i += 1
        finally:
            conn.close()
            out[who] = mine

    def _run(self, stop_at, count) -> tuple[list[Request], float]:
        out: list = [None] * self.clients
        t_zero = time.perf_counter()
        threads = [threading.Thread(target=self._client,
                                    args=(w, t_zero, stop_at, count, out))
                   for w in range(self.clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return [r for mine in out for r in mine], t_zero

    def one_pass(self) -> list[Request]:
        """Every query of the pool once, through the cell's own clients."""
        n = len(self.bodies)
        per = -(-n // self.clients)
        return self._run(None, per)[0]

    def window(self, seconds: float) -> tuple[list[Request], float]:
        """-> (requests sent within `seconds`, each waited for; t_zero on
        time.perf_counter)."""
        return self._run(seconds, None)
