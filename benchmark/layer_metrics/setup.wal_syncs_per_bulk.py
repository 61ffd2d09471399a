"""Write path: WAL syncs a `_bulk` request, at window start: the counter
`es.wal.syncs` over `es.bulk.requests`. Set-up writes through `_bulk` alone,
so 1.0 where a request's records are synced once, before it is acknowledged
(the reference's `index.translog.durability: request`), and the request's
document count where every document is synced. Nothing where the server
ships no such counters."""

SYNCS = "es.wal.syncs"
REQUESTS = "es.bulk.requests"


def read(run):
    counters = run.before.get("counters", {})
    syncs, requests = counters.get(SYNCS), counters.get(REQUESTS)
    if syncs is None or not requests:
        return None
    return syncs / requests
