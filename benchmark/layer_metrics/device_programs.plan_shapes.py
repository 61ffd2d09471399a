"""Device programs: plan shapes the warm-up met, each one compiled program
(`es.jit.cache.search_solo.misses` when the window starts)."""

COUNTER = "es.jit.cache.search_solo.misses"


def read(run):
    return run.before.get("counters", {}).get(COUNTER)
