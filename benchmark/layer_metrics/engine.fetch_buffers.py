"""Host planning, dispatch and fetch: device arrays a search's `jax.device_get`
pulled, each one device-to-host copy to wait for (`es.search.fetch.buffers`,
which every unpacked fetch adds to, over the searches of the window). 1.0 where
the program hands its result tree back in one buffer. Nothing where the server
ships no such counter."""

BUFFERS = "es.search.fetch.buffers"
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
