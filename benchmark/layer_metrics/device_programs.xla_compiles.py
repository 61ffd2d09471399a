"""Device programs: programs XLA itself had compiled when the window
started: `es.jit.compiles` less `es.jit.persistent_cache_hits`, the programs
JAX's persistent cache served (the compile listener fires for those too).
Nothing where the server ships no such counter."""


def read(run):
    counters = run.before.get("counters", {})
    hits = counters.get("es.jit.persistent_cache_hits")
    if hits is None or "es.jit.compiles" not in counters:
        return None
    return counters["es.jit.compiles"] - hits
