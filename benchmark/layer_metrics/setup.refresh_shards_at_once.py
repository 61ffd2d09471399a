"""Write path: shards a refresh builds at once, at window start: the sum of
the shards' own build time (`es.refresh.shard_build.ns`: analysis and pack
build of each shard, on whichever thread ran it) over the wall time of the
build stage that held them (`es.refresh.build_wall.ns`). About 1 on one shard
or where the shards are built one after another, towards the number of shards
where they are built side by side. Nothing where the server ships no such
counters."""

SHARDS = "es.refresh.shard_build.ns"
WALL = "es.refresh.build_wall.ns"


def read(run):
    counters = run.before.get("counters", {})
    shards, wall = counters.get(SHARDS), counters.get(WALL)
    if shards is None or not wall:
        return None
    return shards / wall
