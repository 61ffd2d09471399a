"""Host planning, dispatch and fetch: host arrays a search hands its compiled
program, each one host-to-device transfer (`es.search.dispatch.buffers`, which
every dispatch adds to, over the searches of the window). 1.0 where the plan's
parameters cross in one buffer. Nothing where the server ships no such counter."""

BUFFERS = "es.search.dispatch.buffers"
SEARCHES = "es.span.rest.search.count"


def _added(run, key: str):
    after = run.after.get("counters", {}).get(key)
    if after is None:
        return None
    return after - (run.before.get("counters", {}).get(key) or 0)


def read(run):
    buffers, searches = _added(run, BUFFERS), _added(run, SEARCHES)
    if buffers is None or not searches:
        return None
    return buffers / searches
