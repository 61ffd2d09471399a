"""Write path (engine.bulk, WAL): documents acknowledged per second over the
whole `_bulk` loop of set-up, harness clock."""


def read(run):
    s = run.setup
    return s["docs"] / s["load_s"] if s.get("load_s") else None
