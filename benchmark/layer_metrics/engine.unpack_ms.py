"""Host planning, dispatch and fetch: milliseconds a search spends unpacking
its program's fetched outputs (span `engine.unpack`, `_fetched`). 0 where no
search took the solo path; nothing where the server ships no such counter."""

from benchlib import waves


def read(run):
    return waves.mean(run, ["es.span.engine.unpack.ns"],
                      "es.span.rest.search.count", 1e-6)
