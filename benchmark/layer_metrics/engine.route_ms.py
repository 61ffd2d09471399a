"""Host planning, dispatch and fetch: milliseconds a search spends on the
engine thread before its parsing (span `engine.route`): the index's
resolution, the refresh check, the recovery wrapper, the request cache's
look-up. 0 where no search took the solo path; nothing where the server ships
no such counter."""

from benchlib import waves


def read(run):
    return waves.mean(run, ["es.span.engine.route.ns"],
                      "es.span.rest.search.count", 1e-6)
