"""Host planning, dispatch and fetch: milliseconds of a search's
`engine.search` that none of its stages names: the span less `engine.route`,
`parse`, `plan`, `account`, `dispatch`, `fetch`, `unpack` and `collect`. On
the solo path the calls between the stages and the spans' own cost; for a
search that a wave served, the wait for the wave (its members' shares and the
device). 0 where no search ended; nothing where the server ships one of the
counters not at all."""

from benchlib import waves

CHILDREN = ("engine.route", "engine.parse", "engine.plan", "engine.account",
            "engine.dispatch", "engine.fetch", "engine.unpack",
            "engine.collect")


def read(run):
    whole = waves.added(run, "es.span.engine.search.ns")
    parts = [waves.added(run, f"es.span.{c}.ns") for c in CHILDREN]
    searches = waves.added(run, "es.span.rest.search.count")
    if whole is None or searches is None or any(p is None for p in parts):
        return None
    return (whole - sum(parts)) / searches * 1e-6 if searches else 0.0
