"""REST front end: milliseconds the `_search` handler spends on the event
loop, from reading the body to the response object (span `rest.search` less
`engine.queue` and `engine.search`). What `rest.outside_took_ms` holds beyond
it is aiohttp, the sockets and the harness's client."""

from benchlib import spans


def read(run):
    parts = [spans.stage_ms(run, s)
             for s in ("rest.search", "engine.queue", "engine.search")]
    if any(p is None for p in parts):
        return None
    return parts[0] - parts[1] - parts[2]
